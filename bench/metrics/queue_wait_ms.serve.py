"""Median time a request waits in the scheduler's queue: per request
whose first ``repro.serve.prefill`` span starts in the window, that start
minus the end of its ``repro.serve.submit`` span (same ``rid``).  The
count of requests goes to stderr (≈ 20 in a 6 s window at 3.6/s, too
few for a p90).  Moves ttft_p90_ms."""

import statistics

from bench import program_trace
from bench.harness import log


def read(trace, record):
    if record["kind"] != "serve":
        return None
    pt = program_trace.of(trace)
    if pt is None:
        return None
    waits = sorted(pt.queue_waits().values())
    if not waits:
        return None
    log(f"queue_wait_ms.serve: median of {len(waits)} requests "
        f"(min {waits[0]:.1f} ms, max {waits[-1]:.1f} ms)")
    return statistics.median(waits)
