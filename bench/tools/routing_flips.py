#!/usr/bin/env python3
"""The share of (token, choice) routing pairs on which the program and the
float32 reference disagree at step 1.

    python3 bench/tools/routing_flips.py --workload train.moe.ckpt \
        --seeds 1,2,3

For each seed: the cell's weights and first batch, the program's forward
(its own compute precision, layers unrolled and eager, so that each
layer's chosen experts can be read) and the reference's, each layer fed
its own side's hidden state. A pair is a token's choice; it differs when
the expert is in one side's top-k and not in the other's. Near-ties
between the router's logits flip under bfloat16, and a flip in one
layer moves the input of the next, so the share grows with depth.
One JSON line per seed: the share over all layers and per layer.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness                      # noqa: E402


def program_routes(mc, params, tokens) -> list:
    """Each layer's chosen experts (T, k) in the program's forward."""
    from repro.models import moe
    from repro.models.model import Model
    from repro.models.transformer import ExecConfig
    seen, inner = [], moe.route

    def route(logits, k):
        out = inner(logits, k)
        seen.append(out[2])
        return out
    moe.route = route
    try:
        Model(mc, ExecConfig(scan_layers=False, remat_policy="none")) \
            .forward(params, {"tokens": tokens})
    finally:
        moe.route = inner
    return seen


def reference_routes(params, tokens, cfg) -> list:
    import jax
    import jax.numpy as jnp
    from bench.reference import qwen3_moe as ref
    seen, inner = [], ref.route

    def route(x, router, c, prec):
        out = inner(x, router, c, prec)
        seen.append(out[2].reshape(-1, out[2].shape[-1]))
        return out
    ref.route = route
    try:
        with jax.default_matmul_precision("highest"):
            x = params["embedding"]["table"].astype(jnp.float32)[tokens]
            layers = params["stack"]["layers"]
            for i in range(cfg["num_hidden_layers"]):
                lp = jax.tree.map(lambda t: t[i].astype(jnp.float32),
                                  layers)
                x = ref.layer(x, lp, cfg, "f32")[0]
    finally:
        ref.route = inner
    return seen


def differing(a, b) -> float:
    """Share of the k choices of each token in `a` missing from `b`."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    same = (a[:, :, None] == b[:, None, :]).any(-1)
    return float(1 - same.mean())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = harness.workload(args.workload)
    cfg = harness.config(spec["config"])
    harness.load_repro()
    harness.require_chips(spec["chips"])
    harness.enable_compile_cache()
    arch = harness.arch(cfg)
    drv = harness.driver("train")
    for seed in (int(s) for s in args.seeds.split(",")):
        params = arch.make_weights(cfg, harness.seed_key(seed, 1))
        tokens = drv._feed(cfg, spec["train"], seed).batch(0)["tokens"]
        prog = program_routes(arch.model_config(cfg), params, tokens)
        ref = reference_routes(params, tokens, cfg)
        per_layer = [differing(p, r) for p, r in zip(prog, ref)]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "differ": sum(per_layer) / len(per_layer),
                          "per_layer": per_layer}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
