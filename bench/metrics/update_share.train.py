"""Share of the device's busy time spent in the AdaLomo update kernel
(its stats and update calls), from the trace.  Moves
train_tokens_per_s."""

KERNEL = r"adalomo_update(\.\d+)?$"


def read(trace, record):
    if record["kind"] != "train":
        return None
    busy = trace.busy_s()
    t = trace.op_seconds(KERNEL) / trace.n_devices
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
