"""Serving launcher: batched requests through the ServeEngine."""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-demo")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="exercise serving fault tolerance")
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import Model
    from repro.serve import Request, ServeEngine

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, n_slots=args.slots,
                      max_len=args.max_len)
    for i in range(args.requests):
        eng.submit(Request(rid=i, prompt=list(range(2, 2 + args.prompt_len)),
                           max_new_tokens=args.max_new))
    t0 = time.monotonic()
    steps = 0
    snap = None
    while any(s is not None for s in eng.slots) or eng.queue:
        eng.step()
        steps += 1
        if args.snapshot_every and steps % args.snapshot_every == 0:
            snap = eng.snapshot()
    dt = time.monotonic() - t0
    # count what the engine actually produced, not the nominal request
    # shape: max_len truncation can cut a generation short
    generated = sum(len(r.out) - 1 for r in eng.completed)
    print(json.dumps({
        "arch": cfg.name, "requests": args.requests,
        "completed": len(eng.completed),
        "engine_steps": steps, "wall_s": round(dt, 3),
        "tokens_generated": generated,
        "tokens_per_s": round(generated / dt, 1),
        "snapshot_taken": snap is not None,
    }, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
