"""Model FLOP/s utilization of the training window: the benchmark's
forward+backward FLOPs of the window's steps (real tokens, no
recompute) over the window's host time and the chip's bf16 peak.
Moves train_tokens_per_s."""


def read(trace, record):
    if record["kind"] != "train":
        return None
    w = record["window"]
    if w["seconds"] <= 0 or w["steps"] == 0:
        return None
    return 100.0 * w["flops"] / w["seconds"] / (
        record["peaks"]["bf16_flops_per_s"] * trace.n_devices)
