"""Share of the engine's time in which the running batch does not
decode because prefill is serial: the ``repro.serve.prefill`` spans over
the ``repro.serve.step`` spans, both inside the window.  Moves
tpot_p90_ms."""

from bench import program_trace


def read(trace, record):
    if record["kind"] != "serve":
        return None
    pt = program_trace.of(trace)
    if pt is None:
        return None
    step = pt.span_seconds("repro.serve.step")
    prefill = pt.span_seconds("repro.serve.prefill")
    if step <= 0 or prefill <= 0:
        return None
    return 100.0 * prefill / step
