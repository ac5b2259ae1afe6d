"""Model FLOPs of the train steps that ran in the traced window, over
the window's seconds times the chips' bf16 peak, in percent. The FLOPs
are bench/flops.py's (no recompute, half the causal square)."""
from bench.trace import programs_matching


def read(view):
    runs, _ = programs_matching(view["trace"], r"train_step")
    if not runs:
        return None
    chips = view["chips"]
    steps = runs / view["trace"]["devices"]
    return 100 * steps * view["records"]["flops_per_step"] / (
        view["trace"]["window_s"] * chips
        * view["peaks"]["bf16_flops_per_s"])
