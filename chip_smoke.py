#!/usr/bin/env python3
"""Drive the fault-tolerant trainer and the serve engine once on the chip.

    python chip_smoke.py              one chip: the reference, train and
                                      serve phases
    python chip_smoke.py --chips 4    four chips: data-parallel training
                                      with the buddy memory checkpoint only

Reference (one chip): the loss of one batch of 64 tokens through the
train and serve models below, on the chip, agrees with the host CPU's.

Train (one chip): paper-demo at full width trains 8 steps of 8 x 1024
tokens with a checkpoint every step (a full frame, then a delta frame).
A fault-free run, a process failure recovered by `reinit` and a node
failure recovered by `cr` (file reload and recompile) must agree bit for
bit: every step's loss and the final state digest.

Serve (one chip): qwen2-7b at published widths, with 4 of its 28 layers,
answers 16 seeded requests on 8 slots of 2048 positions. A snapshot
taken mid-run and restored into a fresh engine must finish every request
with the token stream of the uninterrupted run.

Four chips: paper-demo on a 4-way data mesh, where the buddy checkpoint
is a ppermute ring over the chips; a fault-free run and a process-kill
run restored from the buddy must agree bit for bit.

Each phase prints JSON lines. The last line of standard output is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}},
printed only when every phase ran and every check held. Without a TPU,
or away from the checkout's `src/`, the script exits non-zero and prints
no such line. Everything runs in this one process, because a chip
belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
WORK = os.path.join(ROOT, ".chip_smoke")      # checkpoints of a run

# the Pallas kernels of the chip path, by the module that holds them: the
# checkpoint path's digests and gather, and the model step's flash
# attention (forward, and the backward's dK/dV and dQ kernels)
PALLAS_MODULES = {
    "repro.kernels.checksum.kernel": ("checksum_kernel",
                                      "tile_checksum_kernel",
                                      "gather_tiles_kernel"),
    "repro.kernels.flash_attention.kernel": ("flash_fwd", "flash_bwd_dkv",
                                             "flash_bwd_dq"),
}
PALLAS_KERNELS = tuple(n for names in PALLAS_MODULES.values()
                       for n in names)
# (run name, recovery strategy, failure kind or None)
TRAIN_RUNS = (("fault-free", "reinit", None),
              ("reinit-process", "reinit", "process"),
              ("cr-node", "cr", "node"))
SERVE_LAYERS = 4


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes() -> list:
    """`peak_bytes_in_use` of every local device, None where the backend
    keeps no statistics."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


@contextlib.contextmanager
def compile_seconds():
    """Seconds XLA spent compiling inside the block (box[0])."""
    import jax
    box = [0.0]

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            box[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def count_pallas_calls():
    """Count the calls of the chip path's Pallas kernels. Each is looked
    up in its module at the call (`kernels.checksum.ops` imports the
    digest kernels there; the flash custom VJP calls its kernels as module
    globals), so wrapping the module attributes sees every call. A digest
    counts once a dispatch; a flash kernel once each time a step program
    that holds it is traced."""
    import importlib
    counts = dict.fromkeys(PALLAS_KERNELS, 0)
    orig = {}

    def counted(name, fn):
        def call(*args, **kwargs):
            counts[name] += 1
            return fn(*args, **kwargs)
        return call

    for modname, names in PALLAS_MODULES.items():
        mod = importlib.import_module(modname)
        for n in names:
            orig[mod, n] = getattr(mod, n)
            setattr(mod, n, counted(n, orig[mod, n]))
    try:
        yield counts
    finally:
        for (mod, n), f in orig.items():
            setattr(mod, n, f)


def _frame_kinds(ckpt_dir: str) -> dict:
    """{"full": n, "delta": m} over the committed step manifests."""
    from repro.checkpoint.manifest import Manifest
    kinds = {"full": 0, "delta": 0}
    for p in glob.glob(os.path.join(ckpt_dir, "step_*", "manifest.json")):
        with open(p) as f:
            kinds[Manifest.from_json(f.read()).kind] += 1
    return kinds


# ------------------------------------------------------------ reference

def reference_phase(cfgs, *, batch: int = 2, seq: int = 64, seed: int = 0,
                    rtol: float = 1e-2) -> list:
    """The loss of one small batch on the default device against the
    same computation on the host CPU, XLA's plain reference, for each
    config. bf16 matmuls accumulate in another order on each backend,
    hence a relative tolerance rather than bit identity."""
    import jax
    from repro.models.model import Model
    from repro.train import TokenPipeline

    cpu = jax.devices("cpu")[0]
    lines = []
    for cfg in cfgs:
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        data = TokenPipeline(cfg.vocab_size, batch, seq, seed=seed).batch(0)
        loss = jax.jit(lambda p, b: model.loss_fn(p, b)[0])
        dev = float(loss(params, data))
        ref = float(loss(*jax.device_put((params, data), cpu)))
        rel = abs(dev - ref) / abs(ref)
        lines.append({"arch": cfg.name, "n_layers": cfg.n_layers,
                      "d_model": cfg.d_model, "batch": batch, "seq": seq,
                      "loss": dev, "cpu_loss": ref, "rel_diff": rel})
        check(math.isfinite(dev) and math.isfinite(ref),
              f"{cfg.name}: non-finite loss {dev} / {ref}")
        check(rel <= rtol, f"{cfg.name}: loss {dev} on "
              f"{jax.default_backend()} vs {ref} on the CPU")
    return lines


# ---------------------------------------------------------------- train

def train_phase(cfg, *, batch: int, seq: int, steps: int, workdir: str,
                runs=TRAIN_RUNS, delta_every: int = 2, mesh=None,
                rules=None, seed: int = 0) -> dict:
    """Train `cfg` through `Trainer` once per entry of `runs` and check
    each faulty run against the first (fault-free) one, bit for bit.
    Returns {"runs": [per-run lines], "pallas": summed kernel counts}."""
    import jax
    from repro.checkpoint.manifest import tree_digest
    from repro.core import FailureType, FaultInjector
    from repro.models.model import Model
    from repro.train import AdamWConfig, TokenPipeline, TrainConfig, Trainer

    model = Model(cfg)
    data = TokenPipeline(cfg.vocab_size, batch, seq, seed=seed)
    opt = AdamWConfig(total_steps=steps, warmup_steps=max(steps // 4, 1))
    ref = None
    lines, pallas = [], dict.fromkeys(PALLAS_KERNELS, 0)
    for name, strategy, fail in runs:
        injector = None
        if fail is not None:
            injector = FaultInjector(
                n_ranks=8, n_steps=steps, seed=seed + 3,
                kind=FailureType.NODE if fail == "node"
                else FailureType.PROCESS)
        tc = TrainConfig(total_steps=steps,
                         ckpt_dir=os.path.join(workdir, name),
                         ckpt_every=1, ckpt_delta_every=delta_every,
                         ckpt_rebase_after=1 if delta_every > 1 else 0,
                         strategy=strategy, seed=seed)
        with compile_seconds() as comp, count_pallas_calls() as calls:
            t0 = time.monotonic()
            tr = Trainer(model, data, opt, tc, mesh=mesh, rules=rules,
                         injector=injector)
            # AdamW with weight decay dirties every tile of the state on
            # every step, so the planner would rightly degrade each delta
            # save to a full frame; lifting the bound makes every second
            # save a delta frame, whose dirty tiles the device gathers
            tr.file_ckpt.delta_max_dirty = 1.0
            res = tr.run()
            wall = time.monotonic() - t0
            digest = tree_digest(tr.state)               # on the device
        host_digest = tree_digest(jax.device_get(tr.state))
        losses = [(log.step, log.loss) for log in tr.logs]
        kinds = _frame_kinds(tc.ckpt_dir)
        rebase = dict(tr.file_ckpt.last_rebase)
        tr.file_ckpt.close()
        line = {
            "run": name, "strategy": strategy, "fail": fail,
            "final_step": res["final_step"],
            "final_loss": losses[-1][1] if losses else None,
            "digest": digest, "frames": kinds,
            "rebase_ok": rebase.get("ok"),
            "compile_s": comp[0], "wall_s": wall,
            "pallas": dict(calls),
        }
        if injector is not None:
            rep = res["reports"][0] if res["reports"] else None
            # the first step run after the recovery (cr recompiles in it)
            resumed = [log.seconds for log in tr.logs
                       if rep and log.step == rep.rollback_step + 1]
            line.update(
                fail_step=injector.fail_step,
                rollback_step=rep.rollback_step if rep else None,
                recovery_s=rep.total_s if rep else None,
                first_step_after_recovery_s=(
                    resumed[-1] if resumed else None))
        del tr, res                 # free the device state for the next run
        gc.collect()
        shutil.rmtree(tc.ckpt_dir, ignore_errors=True)

        check(line["final_step"] == steps, f"{name}: stopped early")
        check(all(math.isfinite(v) for _, v in losses),
              f"{name}: non-finite loss")
        check(digest == host_digest,
              f"{name}: device digest {digest} != host digest "
              f"{host_digest}")
        check(rebase.get("ok") is not False,
              f"{name}: background re-base failed: {rebase}")
        if delta_every > 1:
            check(kinds["full"] > 0 and kinds["delta"] > 0,
                  f"{name}: frames written {kinds}")
            check(rebase.get("ok") is True, f"{name}: no re-base ran")
        if ref is None:
            check(fail is None, "the first run is the fault-free reference")
            ref = {"losses": dict(losses), "digest": digest}
        else:
            check(line["rollback_step"] == injector.fail_step,
                  f"{name}: rolled back to {line['rollback_step']}, "
                  f"failed at {injector.fail_step}")
            check(all(ref["losses"].get(s) == v for s, v in losses),
                  f"{name}: losses differ from the fault-free run")
            check(digest == ref["digest"],
                  f"{name}: final state {digest} != fault-free "
                  f"{ref['digest']}")
            line["bit_identical"] = True
        for k, n in calls.items():
            pallas[k] += n
        lines.append(line)
    return {"runs": lines, "pallas": pallas}


# ---------------------------------------------------------------- serve

def serve_phase(cfg, *, n_slots: int = 8, max_len: int = 2048,
                n_requests: int = 16, prompt_lens=(128, 256, 384, 512),
                max_new: int = 32, snapshot_tick: int = 20,
                seed: int = 0) -> dict:
    """Serve seeded requests through `ServeEngine`; snapshot the engine
    at `snapshot_tick`, restore the snapshot into a fresh engine, and
    check that it finishes every live and queued request with the
    uninterrupted run's token stream. Prompt lengths come from a few
    buckets so prefill compiles once per bucket."""
    import jax
    import numpy as np
    from repro.models.model import Model
    from repro.serve import Request, ServeEngine

    rng = np.random.default_rng(seed)
    lens = rng.choice(prompt_lens, size=n_requests)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]
    model = Model(cfg)
    with compile_seconds() as comp:
        t0 = time.monotonic()
        params = model.init(jax.random.PRNGKey(seed))
        eng = ServeEngine(model, params, n_slots=n_slots, max_len=max_len)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=max_new))
        snap, done_at_snap, ticks = None, set(), 0
        while eng.queue or any(s is not None for s in eng.slots):
            eng.step()
            ticks += 1
            if ticks == snapshot_tick:
                snap = eng.snapshot()
                done_at_snap = {r.rid for r in eng.completed}
        streams = {r.rid: list(r.out) for r in eng.completed}
        check(snap is not None, f"run ended before tick {snapshot_tick}")
        fresh = ServeEngine(model, params, n_slots=n_slots,
                            max_len=max_len)
        fresh.restore(snap)
        replayed = {r.rid: list(r.out) for r in fresh.run_until_drained()}
        wall = time.monotonic() - t0
    live = sum(s is not None for s in snap["slots"])
    line = {
        "arch": cfg.name, "n_layers": cfg.n_layers,
        "d_model": cfg.d_model, "slots": n_slots, "max_len": max_len,
        "requests": n_requests, "prompt_lens": sorted(set(map(int, lens))),
        "new_tokens": max_new, "engine_steps": ticks,
        "snapshot_tick": snapshot_tick, "live_at_snapshot": live,
        "queued_at_snapshot": len(snap["queue"]),
        "tokens": sum(len(s) for s in streams.values()),
        "compile_s": comp[0], "wall_s": wall,
    }
    check(sorted(streams) == list(range(n_requests)),
          "not every request completed")
    check(all(len(s) == max_new + 1 for s in streams.values()),
          "a request stopped short of its new tokens")
    check(all(0 <= t < cfg.vocab_size for s in streams.values() for t in s),
          "a token outside the vocabulary")
    check(live > 0 and snap["queue"],
          "the snapshot holds no live slot or no queued request")
    check(set(replayed) | done_at_snap == set(streams)
          and not set(replayed) & done_at_snap,
          "the restored engine finished a different set of requests")
    check(all(replayed[r] == streams[r] for r in replayed),
          "restored token streams differ from the uninterrupted run")
    line["bit_identical"] = True
    return line


# ----------------------------------------------------------------- main

def _load_repro():
    """Import `repro` from this checkout's src/ and nowhere else."""
    pkg = os.path.join(SRC, "repro")
    check(os.path.isdir(pkg), f"no repro package at {pkg}")
    sys.path.insert(0, SRC)
    # `repro` is a namespace package (no __init__.py): check a module
    import repro.configs
    where = os.path.dirname(os.path.abspath(repro.configs.__file__))
    check(os.path.dirname(where) == pkg,
          f"repro imported from {where}, not {pkg}")


def _one_chip(seed: int, workdir: str):
    from repro.configs import get_config
    train_cfg = get_config("paper-demo")
    published = get_config("qwen2-7b")
    serve_cfg = dataclasses.replace(published, n_layers=SERVE_LAYERS)
    for line in reference_phase([train_cfg, serve_cfg], batch=1, seq=64,
                                seed=seed):
        emit("reference", **line)

    out = train_phase(train_cfg, batch=8, seq=1024, steps=8,
                      workdir=workdir, seed=seed)
    for line in out["runs"]:
        emit("train", **line)
    emit("train-done", pallas=out["pallas"], peak_bytes_in_use=peak_bytes())
    # at these shapes the dK/dV kernel accumulates dQ as well, so the
    # separate dQ kernel has no call
    for k, n in out["pallas"].items():
        check(n > 0 or k == "flash_bwd_dq", f"Pallas {k} never ran")

    emit("serve-config", arch=serve_cfg.name, n_layers=serve_cfg.n_layers,
         published_layers=published.n_layers,
         cut=f"n_layers {published.n_layers} -> {serve_cfg.n_layers}; "
             "widths as published")
    line = serve_phase(serve_cfg, seed=seed)
    emit("serve", **line, peak_bytes_in_use=peak_bytes())


def _four_chips(seed: int, workdir: str):
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import ShardingRules
    mesh = make_host_mesh((4,), ("data",))
    rules = ShardingRules(batch="data", embed="data")
    out = train_phase(get_config("paper-demo"), batch=8, seq=1024, steps=8,
                      workdir=workdir, runs=TRAIN_RUNS[:2], delta_every=0,
                      mesh=mesh, rules=rules, seed=seed)
    for line in out["runs"]:
        emit("train-4chip", mesh=dict(mesh.shape), **line)
    # a Mosaic kernel cannot be partitioned over the mesh, so the digests
    # of the sharded state and the attention of the sharded step take
    # their jnp paths: the counts stay 0 here
    emit("train-4chip-done", pallas=out["pallas"],
         peak_bytes_in_use=peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip buddy-checkpoint path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # the reference phase needs the host CPU backend beside the chip;
    # devices()[0] is still the first platform named
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    try:
        _load_repro()
        from repro.launch.compile_cache import enable_compile_cache
        emit("setup", compile_cache=enable_compile_cache(),
             platform=dev.platform, kind=dev.device_kind,
             count=len(devices), jax=jax.__version__)
        os.makedirs(WORK, exist_ok=True)
        with tempfile.TemporaryDirectory(dir=WORK) as workdir:
            if args.chips == 4:
                _four_chips(args.seed, workdir)
            else:
                _one_chip(args.seed, workdir)
    except Exception:               # any failed phase or check: no ok line
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
