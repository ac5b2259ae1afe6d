"""The training window: `Trainer.run()` with checkpoints.

Set-up builds one `Trainer` with weights made from the seed, drives it
through its first three steps (the steps the reference follows), warms
one save, and times a few steps to size the window. The window is one
`Trainer.run()` continuation of as many steps as fill `--seconds`; the
same object runs it. The workload states its save interval in seconds
(`save_every_s`), as a job sets it from its failure rate; the steps
between saves follow from the step time measured in set-up, and no save
falls in set-up's timed steps.

After the window, `correct` compares:
  loss_rel          each of the first three steps' loss against the
                    float32 reference, relative;
  grad_norm_gap     the first gradient as AdamW got it (its first moment
                    over 1-b1), by the worst leaf, against the reference;
  grad_median_leaf_gap  the same, by the median leaf;
  update_norm_gap   the parameters' change over the three steps, by the
                    worst leaf, against the reference;
  update_median_leaf_gap  the same, by the median leaf;
  ckpt_leaves_differ the newest committed file checkpoint read back,
                    against the device copy of the state at its step.
A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf. The worst leaf
catches one leaf left unmoved or moved twice; the median leaf is the
steady reading that a lower precision moves, where the worst swings
with the bfloat16 round-off of the small bias leaves. The last is an
exact comparison, with the limit 0; the workload file holds the others'.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time

import numpy as np

from bench import harness
from bench.traffic.tokens import TokenFeed
from bench.weights import check_layout

WARM_TIMED_STEPS = 8          # steps timed in set-up to size the window
FIRST_STEPS = 3               # steps the reference follows
NO_SAVE = 2**31 - 1           # the save interval in steps until it is set


def _leaf_norms(tree, scale: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t):
        return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(
            jnp.square(x.astype(jnp.float32) * scale)))
            for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    return {k: float(v) for k, v in norms(tree).items()}


def _diff_norms(a, b) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(x, y):
        return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
            u.astype(jnp.float32) - v.astype(jnp.float32))))
            for (p, u), v in zip(jax.tree_util.tree_flatten_with_path(x)[0],
                                 jax.tree.leaves(y))}
    return {k: float(v) for k, v in norms(a, b).items()}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> list[float]:
    """|prog - ref| / max(ref leaf, median ref leaf), leaf by leaf."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in ref]))
    return [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys]


def first_step_readings(prog: dict, ref: dict) -> dict:
    """The numbers that compare the first steps with a reference. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out of the change: under AdamW they move by round-off
    alone."""
    g_med = float(np.median(list(ref["grad"].values())))
    moving = {k for k, g in ref["grad"].items() if g >= 1e-3 * g_med}
    grad = leaf_gaps(prog["grad"], ref["grad"])
    update = leaf_gaps(prog["update"], ref["update"], moving)
    return {
        "loss_rel": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": max(grad),
        "grad_median_leaf_gap": float(np.median(grad)),
        "update_norm_gap": max(update),
        "update_median_leaf_gap": float(np.median(update)),
    }


def reference_first_steps(cfg: dict, spec: dict, seed: int,
                          prec: str = "f32", rows=None) -> dict:
    """The reference's losses, first clipped gradient and change over the
    first steps, from the same seed's weights and batches (only their
    first `rows` rows when given: the half-batch fault)."""
    import jax
    arch = harness.arch(cfg)
    with jax.default_matmul_precision("highest"):
        params = arch.make_weights(cfg, harness.seed_key(seed, 1))
        feed = _feed(cfg, spec["train"], seed)
        batches = [{k: v[:rows] for k, v in feed.batch(s).items()}
                   for s in range(FIRST_STEPS)]
        losses, g1, p3 = arch.train(params, batches, cfg, spec["optimizer"],
                                    prec)
        out = {"losses": losses, "grad": _leaf_norms(g1),
               "update": _diff_norms(p3, params)}
        del params, g1, p3
    return out


def _feed(cfg: dict, tspec: dict, seed: int) -> TokenFeed:
    return TokenFeed(harness.seed_key(seed, 2), cfg["vocab_size"],
                     tspec["batch"], tspec["seq"])


class TrainCell:
    """One Trainer and everything the window and the checks read."""

    def __init__(self, ctx):
        import jax.numpy as jnp
        from repro.models.model import Model
        from repro.train import AdamWConfig, TrainConfig, Trainer
        from repro.train.optimizer import adamw_init

        cfg, spec = ctx.config, ctx.workload
        t = spec["train"]
        arch = self.arch = harness.arch(cfg)
        model = Model(arch.model_config(cfg))
        self.feed = _feed(cfg, t, ctx.seed)
        self.opt = AdamWConfig(**spec["optimizer"])
        params = arch.make_weights(cfg, harness.seed_key(ctx.seed, 1))
        check_layout(params, model.abstract_params())
        self.tc = TrainConfig(
            total_steps=0, ckpt_dir=os.path.join(ctx.work_dir, "ckpt"),
            ckpt_every=NO_SAVE, ckpt_shards=t["ckpt_shards"],
            ckpt_delta_every=0, async_file_ckpt=True,
            strategy=t["strategy"], seed=0)
        tr = self.tr = Trainer(model, self.feed, self.opt, self.tc)
        tr.state = {"params": params, "opt": adamw_init(params),
                    "step": jnp.zeros((), jnp.int32)}
        spans = ctx.spans
        spans.wrap(tr, "_save_ckpt", "save")
        spans.wrap(tr, "_step", "step_dispatch")
        spans.wrap(self.feed, "batch", "feed")
        self.d2h: list[tuple[float, int]] = []
        inner_write = tr.file_ckpt._write

        def write(*args, **kwargs):            # on the writer thread
            out = inner_write(*args, **kwargs)
            self.d2h.append((time.perf_counter(),
                             tr.file_ckpt.last_write["d2h_bytes"]))
            return out
        tr.file_ckpt._write = write
        self.digested: list[tuple[float, int]] = []
        self._wrap_checksum_kernel()

    def _wrap_checksum_kernel(self):
        """Bytes handed to the Pallas digest kernel, per call. The
        program's dispatcher imports the kernel from its module at each
        call, so the module attribute sees every dispatch."""
        from repro.kernels.checksum import kernel as kmod
        inner = self._kernel = kmod.checksum_kernel

        def counted(words, *args, **kwargs):
            self.digested.append((time.perf_counter(),
                                  int(words.size) * 4))
            return inner(words, *args, **kwargs)
        kmod.checksum_kernel = counted

    def close(self):
        """Drain the writer, drop the program's state, unwrap the kernel."""
        from repro.kernels.checksum import kernel as kmod
        kmod.checksum_kernel = self._kernel
        if self.tr is not None:
            self.tr.file_ckpt.close()
            self.tr = None
        gc.collect()
        shutil.rmtree(self.tc.ckpt_dir, ignore_errors=True)

    def run_to(self, total: int) -> dict:
        self.tr.tc = dataclasses.replace(self.tc, total_steps=total)
        return self.tr.run()

    def step(self) -> int:
        return int(self.tr.state["step"])


def program_first_steps(cell: TrainCell) -> dict:
    """Steps 1..3 through the window's own call and feed, with the
    readings the reference is compared on."""
    import jax
    import jax.numpy as jnp
    tr = cell.tr
    p0 = jax.tree.map(jnp.copy, tr.state["params"])
    cell.run_to(1)
    grad = _leaf_norms(tr.state["opt"]["m"], 1.0 / (1.0 - cell.opt.b1))
    cell.run_to(FIRST_STEPS)
    update = _diff_norms(tr.state["params"], p0)
    del p0
    return {"losses": [log.loss for log in tr.logs[:FIRST_STEPS]],
            "grad": grad, "update": update}


def _checkpoint_leaves_differ(tr) -> int:
    """Leaves of the newest committed checkpoint that differ, bit for bit,
    from the device copy of the state at its step (all of them when the
    newest checkpoint is not that step's)."""
    import jax
    tr.file_ckpt.wait()
    mem_step, local, _ = tr.mem_ckpt
    step, state = tr.file_ckpt.load_latest()
    mine = jax.tree_util.tree_flatten_with_path(jax.device_get(local))[0]
    if step != mem_step:
        return len(mine)
    disk = dict((jax.tree_util.keystr(p), x) for p, x in
                jax.tree_util.tree_flatten_with_path(state)[0])
    return sum(1 for p, x in mine
               if not np.array_equal(np.asarray(x),
                                     np.asarray(disk.get(
                                         jax.tree_util.keystr(p)))))


def run(ctx) -> dict:
    cfg, spec = ctx.config, ctx.workload
    t = spec["train"]
    limits = spec["limits"]
    cell = TrainCell(ctx)
    tr, spans = cell.tr, ctx.spans

    with ctx.compiles.armed() as setup_compiles:
        prog = program_first_steps(cell)
        # warm one save, so that nothing compiles inside the window
        tr._save_ckpt(cell.step())
        tr.file_ckpt.wait()
        t_warm = time.perf_counter()
        start = cell.step() + WARM_TIMED_STEPS
        cell.run_to(start)
        step_s = (time.perf_counter() - t_warm) / WARM_TIMED_STEPS

    n_steps = max(1, round(ctx.seconds / step_s))
    every = max(1, round(t["save_every_s"] / step_s))
    tr.policy = dataclasses.replace(tr.policy, every_steps=every)
    n_logs = len(tr.logs)
    trace_dir = os.path.join(ctx.work_dir, "trace")
    with harness.device_trace(ctx.trace, spans, trace_dir):
        with ctx.compiles.armed() as window_compiles, spans.span("window"):
            t0 = time.perf_counter()
            res = cell.run_to(start + n_steps)
            t1 = time.perf_counter()
    device = harness.device_info(ctx.devices)

    logs = tr.logs[n_logs:]
    window_s = t1 - t0
    progress = res["final_step"] - start
    tokens = progress * t["batch"] * t["seq"]
    e2e = {"train_tokens_per_s": tokens / window_s,
           "setup_s": t0 - ctx.t_start}
    records = {
        "steps_run": len(logs),
        "flops_per_step": cell.arch.train_step_flops(cfg, t["batch"],
                                                     t["seq"]),
        "save_s": [b - a for a, b in spans.between("save", t0, t1)],
        "d2h_bytes": [n for at, n in cell.d2h if t0 <= at <= t1],
        "digested_bytes": sum(n for at, n in cell.digested
                              if t0 <= at <= t1),
    }
    ckpt_differ = _checkpoint_leaves_differ(tr)
    harness.note(window_s=window_s, steps=n_steps, start_step=start,
                 warm_step_s=step_s, save_every=every,
                 saves=len(records["save_s"]),
                 compiles_in_window=window_compiles,
                 setup_compiles=setup_compiles, first_steps=prog["losses"])

    # free the program's state before the reference takes the chip
    tr = res = None
    cell.close()

    ref = reference_first_steps(cfg, spec, ctx.seed)
    readings = first_step_readings(prog, ref)
    checks = [{"name": k, "value": v, "limit": limits[k]}
              for k, v in readings.items()]
    checks.append({"name": "ckpt_leaves_differ", "value": ckpt_differ,
                   "limit": 0})
    return {"e2e": e2e, "records": records, "checks": checks,
            "attempted": len(logs), "failed": len(logs) - progress,
            "device": device, "trace_dir": trace_dir}
