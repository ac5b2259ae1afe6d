"""Program spans: the helper's timed handle and its names, and the spans a
short training run with async saves and one process recovery leaves in
the profiler's trace, each on the line of the thread that ran it."""
import glob
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.checkpoint import FileCheckpointer
from repro.configs import get_config, reduced
from repro.core import FailureType, FaultInjector
from repro.core.spans import PREFIX, span, step_span
from repro.models.model import Model
from repro.train import AdamWConfig, TokenPipeline, TrainConfig, Trainer

STEPS = 8
LOOP_CHILDREN = ("train.feed", "train.dispatch", "train.wait",
                 "train.readback", "train.bookkeeping")


def _trace(trace_dir):
    """The profiler around a block, without its Python call tracer."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


def _events(trace_dir) -> list[dict]:
    """Every `repro.` host event of the trace, with the index of its line
    (one line a thread) and its attributes."""
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append({"name": e.name[len(PREFIX):], "line": line,
                                "t0": e.start_ns,
                                "t1": e.start_ns + e.duration_ns,
                                "stats": dict(e.stats)})
    return out


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(e, outer):
    return outer["t0"] <= e["t0"] and e["t1"] <= outer["t1"]


@pytest.mark.parametrize("make", [lambda: span("unit.block"),
                                  lambda: step_span(3)])
def test_handle_times_its_block(make):
    with make() as h:
        time.sleep(0.02)
    assert 0.02 <= h.seconds < 1.0


def test_names_carry_the_prefix_and_attributes(tmp_path):
    with _trace(tmp_path):
        with span("unit.outer", step=7) as h:
            with step_span(5):
                pass
            h.set(bytes=123)
    ev = _events(tmp_path)
    outer, = _named(ev, "unit.outer")
    assert outer["stats"] == {"step": 7, "bytes": 123}
    it, = _named(ev, "train.iter")
    assert it["stats"]["step_num"] == 5 and _inside(it, outer)


def test_training_run_spans(tmp_path, monkeypatch):
    """Iterations and their children on the loop's line, tiling each
    iteration in order; the save and its parts on the same line; every
    write on a writer thread's line, tied to its save by `step`; the
    recovery phases, whose seconds fill the report."""
    # the device digest (and its span) as on an accelerator backend
    monkeypatch.setattr(FileCheckpointer, "_device_digests_on", True)
    cfg = reduced(get_config("paper-demo"))
    inj = FaultInjector(n_ranks=8, n_steps=STEPS, kind=FailureType.PROCESS,
                        seed=3)
    assert 2 < inj.fail_step < STEPS
    tc = TrainConfig(total_steps=STEPS, ckpt_dir=str(tmp_path / "ckpt"),
                     ckpt_every=2, async_file_ckpt=True, strategy="reinit")
    tr = Trainer(Model(cfg), TokenPipeline(cfg.vocab_size, 4, 32, seed=7),
                 AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=STEPS),
                 tc, injector=inj)
    with _trace(tmp_path / "trace"):
        res = tr.run()
    assert res["final_step"] == STEPS
    ev = _events(tmp_path / "trace")

    iters = _named(ev, "train.iter")
    loop = iters[0]["line"]
    assert {e["line"] for e in iters} == {loop}
    # every iteration, the failed one and those replayed included
    rollback = res["reports"][0].rollback_step
    assert len(iters) == STEPS + 1 + (inj.fail_step - rollback)
    for it in iters:
        kids = sorted((e for e in ev if e["line"] == loop and e is not it
                       and _inside(e, it) and e["name"] in LOOP_CHILDREN),
                      key=lambda e: e["t0"])
        if it["stats"]["step_num"] == inj.fail_step and kids == []:
            continue                  # the iteration the failure ended
        assert [e["name"] for e in kids] == list(LOOP_CHILDREN)

    saves = _named(ev, "train.save")
    save_steps = [e["stats"]["step"] for e in saves]
    assert save_steps and all(s % 2 == 0 for s in save_steps)
    for name in ("save.copies", "ckpt.save", "ckpt.backpressure",
                 "ckpt.snapshot", "ckpt.digest"):
        found = _named(ev, name)
        assert len(found) == len(saves), name
        assert all(e["line"] == loop and any(_inside(e, s) for s in saves)
                   for e in found), name
    assert all(e["stats"]["bytes"] > 0 for e in _named(ev, "ckpt.digest"))

    writes = _named(ev, "ckpt.write")
    assert sorted(e["stats"]["step"] for e in writes) == sorted(save_steps)
    assert all(e["line"] != loop for e in writes)
    for w in writes:
        for name in ("ckpt.d2h", "ckpt.digest_fold", "ckpt.shards",
                     "ckpt.commit"):
            part, = [e for e in _named(ev, name) if _inside(e, w)]
            assert part["line"] == w["line"]
            assert part["stats"]["step"] == w["stats"]["step"]
        d2h, = [e for e in _named(ev, "ckpt.d2h") if _inside(e, w)]
        assert d2h["stats"]["bytes"] > 0
    shards = _named(ev, "ckpt.shard")
    assert len(shards) == tc.ckpt_shards * len(writes)
    assert all(e["line"] != loop and e["stats"]["bytes"] > 0
               for e in shards)

    rep, = res["reports"]
    for name, secs in (("recovery.detect", rep.detect_s),
                       ("recovery.mpi", rep.mpi_recovery_s),
                       ("recovery.restore", rep.ckpt_read_s)):
        e, = _named(ev, name)
        assert e["line"] == loop and secs > 0
    drain, = _named(ev, "train.drain")
    assert drain["line"] == loop and drain["t0"] >= iters[-1]["t1"]
