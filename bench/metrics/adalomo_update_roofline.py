"""Roofline share of the AdaLomo update kernel: the least time its
algorithm's bytes and FLOPs need on the chip (bench/counters.py: θ and g
read once, θ written once, O(m+n) moments), over the kernel's device
time, for the training steps whole inside the trace.  Moves
train_tokens_per_s."""

from bench import counters

KERNEL = r"adalomo_update(\.\d+)?$"


def read(trace, record):
    if record["kind"] != "train":
        return None
    steps = trace.modules_named("jit_one_step")
    t = sum(trace.ops_in(m, KERNEL) for m in steps)
    if not steps or t <= 0:
        return None
    work = counters.adalomo_step_work(record["cfg"])
    least, _ = counters.roofline_seconds(
        work["flops"] * len(steps), work["bytes"] * len(steps),
        record["peaks"])
    return 100.0 * least / t
