"""Device idle share of a serving window: 1 - (union of op time) /
window, from the trace.  Moves tpot_p90_ms."""


def read(trace, record):
    if record["kind"] != "serve" or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
