"""Roofline share of the AdaLomo update kernel: the least time its
algorithm's bytes and FLOPs need on the cell's chips (bench/counters.py:
θ and g read once, θ written once, O(m+n) moments; the work of the whole
model once, spread over every chip), over the kernel's device time per
chip, for the training steps whole inside the trace.  A kernel that
every chip runs on whole tensors reads at most 1/chips.  Moves
train_tokens_per_s."""

from bench import counters

KERNEL = r"adalomo_update(\.\d+)?$"
STEP = "jit_one_step"


def read(trace, record):
    if record["kind"] != "train":
        return None
    n = trace.n_devices
    steps = trace.modules_named(STEP)
    t = sum(trace.ops_in(m, KERNEL, d) for d in range(n)
            for m in trace.modules_named(STEP, d)) / n
    if not steps or t <= 0:
        return None
    work = counters.adalomo_step_work(record["cfg"])
    least, _ = counters.roofline_seconds(
        work["flops"] * len(steps) / n, work["bytes"] * len(steps) / n,
        record["peaks"])
    return 100.0 * least / t
