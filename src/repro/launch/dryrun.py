import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first initialization. Everything below is ordinary.

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp                            # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs import ASSIGNED, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.models.config import ShapeConfig        # noqa: E402
from repro.models.flops import cell_cost           # noqa: E402
from repro.models.model import Model               # noqa: E402
from repro.models.transformer import ExecConfig    # noqa: E402
from repro.sharding.partition import (_divisible, constraint_scope,
                                      state_shardings)        # noqa: E402
from repro.sharding.rules import PRESETS           # noqa: E402
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update  # noqa: E402
from repro.launch.hlo_analysis import (  # noqa: E402
    collective_summary, while_report)
from repro.launch.mesh import make_production_mesh  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces a JSON artifact with:
  - memory_analysis (argument/output/temp bytes per device — proves fit),
  - cost_analysis raw numbers (per-device, scan-body-once caveat),
  - the collective schedule from the optimized HLO with while-trip-count
    correction (launch/hlo_analysis.py),
  - analytic FLOPs/bytes from models/flops.py,
  - lowering/compile wall times.

benchmarks/roofline.py consumes these artifacts to build the §Roofline
table.
"""


def _sds(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype) \
        if not isinstance(x, jax.ShapeDtypeStruct) else x


def batch_shardings(mesh, rules, batch):
    """NamedShardings for the input dict (tokens/labels/embeddings…)."""
    out = {}
    for k, v in batch.items():
        if k == "pos":
            out[k] = NamedSharding(mesh, P())
        elif k == "state":
            specs = None      # handled separately
        else:
            spec = P(rules.batch, *(None,) * (len(v.shape) - 1))
            out[k] = NamedSharding(mesh, _divisible(spec, v.shape, mesh))
    return out


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               ec: ExecConfig):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, why
    if shape.kind != "train":
        cfg = cfg.replace(param_dtype="bfloat16")    # serving dtype
    model = Model(cfg, ec)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if shape.kind == "train":
        rules = PRESETS["multipod" if multi_pod else "pod"]
    else:
        rules = PRESETS["multipod_serve" if multi_pod else "pod_serve"]
    # kv heads shard over the model axis only when they divide it evenly
    # (olmoe/seamless: 16 kv heads on a 16-way axis); otherwise they stay
    # replicated and the GQA expansion is local (rules.py comment).
    if (shape.kind == "train" and cfg.n_kv_heads
            and cfg.n_kv_heads % mesh.shape["model"] == 0):
        rules = dataclasses.replace(rules, kv_heads="model")
    return (cfg, shape, model, mesh, rules), ""


def lower_cell(cfg, shape: ShapeConfig, model: Model, mesh, rules,
               donate: bool = True, with_buddy: bool = False):
    """Returns (lowered, meta) for the cell's step function.

    with_buddy=True (train cells) fuses the paper's buddy memory
    checkpoint into the step: the post-update state is collective-permuted
    one step along the data axis and returned as a second output — the
    redundant HBM copy lives on the neighbour chip.
    """
    specs = model.input_specs(shape, abstract=True)

    if shape.kind == "train":
        params_abs = model.abstract_params()
        state_abs = {"params": params_abs,
                     "opt": jax.eval_shape(adamw_init, params_abs),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
        st_sh = state_shardings(mesh, state_abs, rules)
        b_sh = batch_shardings(mesh, rules, specs)
        opt_cfg = AdamWConfig()

        M = model.ec.microbatches

        def grad_of(params, batch):
            def loss_fn(p):
                return model.loss_fn(p, batch)
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, grads

        def train_step(state, batch):
            if M > 1:
                # gradient accumulation: activation live-set shrinks by M,
                # FSDP weight gathers repeat per microbatch (the classic
                # memory ↔ collective trade)
                mb = jax.tree.map(
                    lambda a: a.reshape(M, a.shape[0] // M, *a.shape[1:]),
                    batch)

                def acc(carry, b):
                    gsum, lsum = carry
                    loss, g = grad_of(state["params"], b)
                    return (jax.tree.map(jnp.add, gsum, g),
                            lsum + loss), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                (gsum, lsum), _ = jax.lax.scan(
                    acc, (zeros, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / M, gsum)
                loss = lsum / M
            else:
                loss, grads = grad_of(state["params"], batch)
            new_p, new_opt, om = adamw_update(state["params"], grads,
                                              state["opt"], opt_cfg)
            return ({"params": new_p, "opt": new_opt,
                     "step": state["step"] + 1}, loss)

        if with_buddy:
            from repro.checkpoint.memory_ckpt import buddy_exchange

            def train_step_buddy(state, batch):
                new_state, loss = train_step(state, batch)
                buddy = buddy_exchange(new_state, mesh, rules)
                return new_state, (loss, buddy)

            fn = jax.jit(train_step_buddy, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, (None, st_sh)),
                         donate_argnums=(0,) if donate else ())
        else:
            fn = jax.jit(train_step, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None),
                         donate_argnums=(0,) if donate else ())
        args = (state_abs, specs)

    elif shape.kind == "prefill":
        params_abs = model.abstract_params()
        p_sh = state_shardings(mesh, params_abs, rules)
        b_sh = batch_shardings(mesh, rules, specs)

        def prefill_step(params, batch):
            return model.prefill(params, batch, max_len=shape.seq_len)

        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        args = (params_abs, specs)

    else:  # decode
        params_abs = model.abstract_params()
        p_sh = state_shardings(mesh, params_abs, rules)
        state_abs = specs["state"]
        sspecs = model.decode_state_specs(rules)
        sspecs = jax.tree.map(
            lambda s, leaf: _divisible(s, leaf.shape, mesh),
            sspecs, state_abs, is_leaf=lambda s: isinstance(s, P))
        s_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                            is_leaf=lambda s: isinstance(s, P))
        tok_sh = NamedSharding(mesh, _divisible(
            P(rules.batch, None), specs["token"].shape, mesh))

        def serve_step(params, token, state, pos):
            return model.decode_step(params, token, state, pos)

        fn = jax.jit(serve_step,
                     in_shardings=(p_sh, tok_sh, s_sh, NamedSharding(mesh, P())),
                     out_shardings=(None, s_sh),
                     donate_argnums=(2,) if donate else ())
        args = (params_abs, specs["token"], state_abs, specs["pos"])

    with constraint_scope(mesh, rules):
        t0 = time.monotonic()
        lowered = fn.lower(*args)
        t_lower = time.monotonic() - t0
    return lowered, {"lower_s": t_lower}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_path: str | None = None, ec: ExecConfig | None = None,
             donate: bool = True, save_hlo: str | None = None,
             with_buddy: bool = False) -> dict:
    ec = ec or ExecConfig()
    built, why = build_cell(arch, shape_name, multi_pod, ec)
    mesh_name = "multipod" if multi_pod else "pod"
    if built is None:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "skipped": why}
    else:
        cfg, shape, model, mesh, rules = built
        lowered, meta = lower_cell(cfg, shape, model, mesh, rules,
                                   donate=donate,
                                   with_buddy=with_buddy and
                                   shape.kind == "train")
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        colls = collective_summary(hlo)
        whiles = while_report(hlo)
        ac = cell_cost(cfg, shape, flash=(ec.attn_impl == "pallas"))
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "chips": mesh.size,
            "exec_config": dataclasses.asdict(ec),
            "lower_s": meta["lower_s"], "compile_s": t_compile,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "cost_analysis": {
                "flops_per_device": ca.get("flops", 0.0),
                "bytes_per_device": ca.get("bytes accessed", 0.0),
            },
            "collective_bytes": colls,
            "whiles": whiles,
            "analytic": {
                "flops_total": ac.flops,
                "hbm_bytes_total": ac.hbm_bytes,
                "model_flops": ac.details["model_flops"],
            },
        }
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all", choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="")
    ap.add_argument("--attn-impl", default="chunked")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--no-scan", action="store_true")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--with-buddy", action="store_true",
                    help="fuse the buddy memory checkpoint (a ppermute of "
                         "the train state) into the lowered step")
    ap.add_argument("--save-hlo", default="")
    args = ap.parse_args(argv)

    ec = ExecConfig(attn_impl=args.attn_impl, remat_policy=args.remat,
                    scan_layers=not args.no_scan,
                    microbatches=args.microbatches)
    archs = ASSIGNED if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for arch in archs:
        for shape in shapes:
            out = None
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                out = os.path.join(
                    args.out, f"{arch}__{shape}__{args.mesh}.json")
            try:
                r = run_cell(arch, shape, args.mesh == "multipod",
                             out_path=out, ec=ec,
                             donate=not args.no_donate,
                             save_hlo=args.save_hlo or None,
                             with_buddy=args.with_buddy)
                if "skipped" in r:
                    print(f"[dryrun] {arch} × {shape} × {args.mesh}: "
                          f"SKIP ({r['skipped']})")
                else:
                    print(f"[dryrun] {arch} × {shape} × {args.mesh}: OK "
                          f"compile={r['compile_s']:.1f}s "
                          f"coll={r['collective_bytes'].get('total',0)/1e9:.2f}GB "
                          f"arg={r['memory']['argument_bytes']/1e9:.2f}GB")
            except Exception as e:      # noqa: BLE001
                failures.append((arch, shape, str(e)))
                print(f"[dryrun] {arch} × {shape} × {args.mesh}: "
                      f"FAIL {type(e).__name__}: {e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
