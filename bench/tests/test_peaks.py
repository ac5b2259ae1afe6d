"""The peak table: known kinds with their source, unknown kinds raise."""
import pytest

from bench import harness


def test_v5e_peaks_as_published():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in harness.load_json(
        harness.os.path.join(harness.BENCH, "peaks.json"))["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "TPU v5 Lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(harness.BenchError):
        harness.peaks(kind)
