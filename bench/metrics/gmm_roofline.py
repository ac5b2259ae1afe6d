"""The grouped-matmul kernel's share of its roofline, in percent: the
operations it runs in the traced window at the experts' expected rows
(the architecture module's `gmm_step_flops` times the train steps in the
trace) over its device seconds times the bf16 peak. Its operations are
the Pallas calls whose names hold `gmm` (`gmm` forward and for the
input's gradient, `tgmm` for the weights'). None where the configuration
has no routed experts or the kernel did not run."""
from bench import harness
from bench.trace import ops_matching, programs_matching


def read(view):
    arch = harness.arch(view["config"])
    if not hasattr(arch, "gmm_step_flops"):
        return None
    steps, _ = programs_matching(view["trace"], r"train_step")
    _, secs = ops_matching(view["trace"], r"^%\S*gmm\S* = ")
    if not steps or not secs:
        return None
    t = view["workload"]["train"]
    flops = steps * arch.gmm_step_flops(view["config"], t["batch"], t["seq"])
    return 100 * flops / (secs * view["peaks"]["bf16_flops_per_s"])
