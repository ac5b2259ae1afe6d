"""The program's spans read beside the benchmark's: a hand-made trace of two
threads whose answers are known, the six accepted readers unmoved by
program spans in a record, and the program's byte counts equal to what
the benchmark's wrappers count in the same run."""
import functools
import os

import pytest

from bench import harness, program_spans, trace
from bench.tests.tiny import context

HERE = os.path.dirname(os.path.abspath(__file__))
LOOP, WRITER = "/host:CPU#0", "/host:CPU#1"

# one device, window 0..10 s; train_step runs [1,3) and [5,7)
DEVICES = {"/device:TPU:0": {
    "modules": [["jit_train_step", 1.0, 2.0], ["jit_train_step", 5.0, 2.0],
                ["jit_copy", 8.2, 0.8]],
    "ops": [["fusion.0", 0.02, 0.03], ["fusion.1", 1.0, 2.0],
            ["fusion.1", 5.0, 2.0], ["copy.2", 8.2, 0.8]]}}
# idle gaps: [0,.02) [.05,1) [3,5) [7,8.2) [9,10)
LOOP_SPANS = [
    ("window", 0.0, 10.0),
    ("train.iter", 0.1, 4.1), ("train.feed", 0.1, 0.2),
    ("train.dispatch", 0.3, 0.6), ("train.wait", 0.9, 2.1),
    ("train.readback", 3.0, 0.3), ("train.bookkeeping", 3.3, 0.3),
    ("train.iter", 4.2, 3.8), ("train.feed", 4.2, 0.2),
    ("train.dispatch", 4.4, 0.5), ("train.wait", 4.9, 2.1),
    ("train.readback", 7.0, 0.1), ("train.bookkeeping", 7.1, 0.2),
    ("train.save", 7.3, 0.65), ("save", 7.35, 0.55),
    ("save.copies", 7.4, 0.1), ("ckpt.save", 7.5, 0.4),
    ("train.drain", 8.1, 1.8)]
WRITER_SPANS = [("ckpt.write", 3.7, 1.0), ("ckpt.write", 7.9, 1.9),
                ("ckpt.write", 10.5, 0.5)]


def _host(loop=LOOP_SPANS, writer=WRITER_SPANS, writer_line=WRITER):
    return ([[n, s, d, LOOP, {}] for n, s, d in loop]
            + [[n, s, d, writer_line, {"step": 2}] for n, s, d in writer])


def _rec():
    return {"devices": DEVICES,
            "spans": [[n, s, d] for n, s, d in LOOP_SPANS
                      if "." not in n]}


def test_hand_made_two_thread_trace():
    out = program_spans.reduce(_rec(), _host())
    assert out["idle_by_span"] == pytest.approx({
        "other": 0.02,             # [0, .02): before the first iteration
        "train.dispatch": 0.95,    # [.05, 1)
        "train.iter": 2.0,         # [3, 5): the iteration, in no child
        "ckpt.save": 1.2,          # [7, 8.2): inside the save's parts
        "train.drain": 1.0})       # [9, 10)
    # idle 5.17 s, of which 0.65 s in the save and 1.0 s in the drain,
    # over two steps
    assert out["step_gap_ms"] == pytest.approx(1e3 * (5.17 - 1.65) / 2)
    # the two writes that start in the window
    assert out["writer_ms"] == pytest.approx(1e3 * (1.0 + 1.9) / 2)


def test_a_writer_span_never_names_a_loop_gap():
    names = program_spans.reduce(_rec(), _host())["idle_by_span"]
    alone = program_spans.reduce(_rec(), _host(writer=[]))["idle_by_span"]
    assert names == alone
    # the same spans on the loop's line would name the gap at 4 s
    moved = program_spans.reduce(_rec(), _host(writer_line=LOOP))
    assert moved["idle_by_span"]["ckpt.write"] == pytest.approx(2.0)


def test_without_program_spans_nothing_is_read():
    bench_only = [h for h in _host() if "." not in h[0]]
    out = program_spans.reduce(_rec(), bench_only)
    assert out["step_gap_ms"] is None and out["writer_ms"] is None


@pytest.mark.parametrize("slice_name", ["train_trace_slice.json",
                                        "train_trace_slice_spans.json"])
def test_bisect_names_gaps_as_the_scan_does(slice_name):
    """On the benchmark's own spans, the bisect over the loop's line
    names every gap as `bench.trace.reduce` does."""
    rec = trace.load(os.path.join(HERE, "data", slice_name))
    host = [[n, s, d, LOOP, {}] for n, s, d in rec["spans"]]
    want = trace.reduce(rec)["idle_by_span"]
    got = program_spans.reduce(rec, host)["idle_by_span"]
    assert got == pytest.approx(want)


def _readings(rec, records) -> dict:
    red = trace.reduce(rec)
    view = {"trace": red, "records": records, "chips": 1,
            "peaks": harness.peaks("TPU v5 lite")}
    _, mine = harness.cell_metrics("train.ckpt")
    return {m["name"]: harness.metric_reader(m["name"])(view)
            for m in mine}


def test_accepted_readers_unmoved_by_program_spans():
    """The six accepted per-layer metrics read the same numbers from a
    record that carries the program's spans as from one without."""
    records = {"steps_run": 2, "flops_per_step": 4.9e12,
               "save_s": [0.65], "d2h_bytes": [2349421064],
               "digested_bytes": 2349000000}
    plain = trace.load(os.path.join(HERE, "data", "train_trace_slice.json"))
    t0, _ = trace.window(plain)
    with_spans = dict(plain, host=[[n, t0 + s, d, LOOP, {}]
                                   for n, s, d in LOOP_SPANS])
    spans = trace.load(os.path.join(HERE, "data",
                                    "train_trace_slice_spans.json"))
    without = {k: v for k, v in spans.items() if k != "host"}
    for a, b in ((plain, with_spans), (without, spans)):
        assert _readings(a, records) == _readings(b, records)
        assert len(_readings(a, records)) == 6


def test_recorded_slice_of_a_window_with_its_save():
    """One second of `train.ckpt` on one v5e from just before its save
    (operations under 20 us dropped): the program's spans name all the
    idle the benchmark's left to "other", the save's idle falls in its
    snapshot and digest, and the step gap leaves the save out."""
    rec = trace.load(os.path.join(HERE, "data",
                                  "train_trace_slice_spans.json"))
    out = program_spans.reduce(rec, rec["host"])
    idle = out["idle_by_span"]
    red = trace.reduce(rec)
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])
    assert red["idle_by_span"]["other"] > 0.01 and "other" not in idle
    assert idle["ckpt.snapshot"] + idle["ckpt.digest"] > 0.7
    # two steps start in the slice; 0.79 s of idle, 0.74 s in the save
    assert out["step_gap_ms"] == pytest.approx(21.104, rel=1e-3)
    assert out["writer_ms"] == pytest.approx(1769.41, rel=1e-4)


def test_program_counts_equal_the_bench_wrappers(tmp_path, monkeypatch):
    """In one traced CPU run of the cell, the bytes the program's spans
    carry equal what the benchmark's wrappers count: `ckpt.d2h` bytes
    against `d2h_bytes.train`'s, and `ckpt.digest` kernel bytes against
    the bytes handed to the Pallas digest kernel (forced onto its path
    here, in interpret mode)."""
    from bench.tools import span_probe
    from repro.checkpoint.file_ckpt import FileCheckpointer
    from repro.kernels.checksum import kernel as kmod
    from repro.kernels.checksum import ops
    monkeypatch.setattr(FileCheckpointer, "_device_digests_on", True)
    monkeypatch.setattr(ops, "_pallas_path", lambda n, sharding: n >= 4096)
    monkeypatch.setattr(kmod, "checksum_kernel", functools.partial(
        kmod.checksum_kernel, interpret=True))
    ctx = context("train.ckpt", seed=2**31 + 5, seconds=1.0, trace=True,
                  work_dir=str(tmp_path))
    out = harness.driver("train").run(ctx)
    rec = trace.record(out["trace_dir"])
    host = program_spans.record(out["trace_dir"])
    t0, t1 = trace.window(rec)
    d2h = [h[4]["bytes"] for h in host
           if h[0] == "ckpt.d2h" and t0 <= h[1] < t1]
    assert d2h and sorted(d2h) == sorted(out["records"]["d2h_bytes"])
    digest = [h[4] for h in host
              if h[0] == "ckpt.digest" and t0 <= h[1] < t1]
    assert out["records"]["digested_bytes"] > 0
    assert sum(d["kernel_bytes"] for d in digest) \
        == out["records"]["digested_bytes"]
    assert all(d["bytes"] >= d["kernel_bytes"] for d in digest)
    # the probe's reading of the same trace
    got = span_probe.analyse(rec, host)
    assert got["digest_kernel_bytes"] == out["records"]["digested_bytes"]
    assert [w["d2h_bytes"] for w in got["write"]] == d2h
    assert span_probe.lookup_seconds(rec, host)["same"]
    # a cut of it reduces as a recorded trace does (the CPU's trace has
    # no device plane, so there is no idle to name)
    part = span_probe.cut(rec, host, t0, (t1 - t0) / 2)
    assert trace.reduce(part)["window_s"] == pytest.approx((t1 - t0) / 2)
    assert program_spans.reduce(part, part["host"])["idle_by_span"] \
        == trace.reduce(part)["idle_by_span"] == {}
    assert program_spans.reduce(rec, host)["writer_ms"] > 0
