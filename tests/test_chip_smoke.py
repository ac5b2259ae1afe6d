"""chip_smoke.py on the CPU: its phases at `reduced()` size, and its
refusal to report success without a TPU."""
import importlib.util
import json
import os

import pytest

from repro.configs import get_config, reduced

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_bit_identical(smoke, tmp_path):
    out = smoke.train_phase(reduced(get_config("paper-demo")), batch=2,
                            seq=32, steps=8, workdir=str(tmp_path))
    runs = {r["run"]: r for r in out["runs"]}
    assert list(runs) == ["fault-free", "reinit-process", "cr-node"]
    ref = runs["fault-free"]
    for name in ("reinit-process", "cr-node"):
        r = runs[name]
        assert r["bit_identical"] and r["digest"] == ref["digest"]
        assert r["rollback_step"] == r["fail_step"]
    # a full frame and a delta frame were written, and the re-base ran
    assert all(r["frames"]["full"] and r["frames"]["delta"]
               for r in runs.values())
    assert all(r["rebase_ok"] is True for r in runs.values())
    # the CPU takes the jnp digests: no Pallas kernel is dispatched
    assert not any(out["pallas"].values())


def test_pallas_counter_sees_the_flash_kernels(smoke):
    """The counter wraps the flash custom VJP's kernels too: a gradient
    through the kernel (interpret mode, as the CPU runs it) counts the
    forward and both backward kernels once each."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    kv = jnp.ones((1, 128, 1, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True))

    with smoke.count_pallas_calls() as calls:
        jax.grad(loss, (0, 1, 2))(q, kv, kv)
    assert calls["flash_fwd"] == 1 and calls["flash_bwd_dkv"] == 1
    assert calls["checksum_kernel"] == 0


def test_serve_phase_snapshot_restore_bit_identical(smoke):
    line = smoke.serve_phase(reduced(get_config("qwen2-7b")), n_slots=4,
                             max_len=64, n_requests=8, prompt_lens=(8, 16),
                             max_new=6, snapshot_tick=3)
    assert line["bit_identical"]
    assert line["live_at_snapshot"] > 0 and line["queued_at_snapshot"] > 0
    assert line["tokens"] == 8 * 7


def test_reference_phase_agrees_with_cpu(smoke):
    lines = smoke.reference_phase(
        [reduced(get_config(a)) for a in ("paper-demo", "qwen2-7b")],
        batch=2, seq=16)
    assert [l["arch"] for l in lines] == ["paper-demo-smoke",
                                          "qwen2-7b-smoke"]
    # on the CPU both sides are the same computation
    assert all(l["rel_diff"] == 0.0 for l in lines)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert not any(json.loads(l).get("ok") for l in out.splitlines()
                   if l.startswith("{"))
    assert '"ok"' not in out


def test_load_repro_finds_checkout_src(smoke):
    smoke._load_repro()
    import repro.configs
    assert os.path.abspath(repro.configs.__file__).startswith(
        os.path.join(ROOT, "src", "repro") + os.sep)


def test_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")
