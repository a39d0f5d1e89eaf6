"""Device idle share of a training window: 1 - (union of op time) /
window, from the trace.  Moves train_tokens_per_s."""


def read(trace, record):
    if record["kind"] != "train" or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
