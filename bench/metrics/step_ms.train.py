"""Device milliseconds per run of the jitted train step (trace)."""
from bench.trace import programs_matching


def read(view):
    runs, secs = programs_matching(view["trace"], r"train_step")
    return 1e3 * secs / runs if runs else None
