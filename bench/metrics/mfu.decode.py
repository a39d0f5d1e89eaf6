"""Roofline share of the whole decode step: per step, the larger of its
model FLOPs over the bf16 peak and its least bytes (every weight once,
the live KV pages, bench/counters.py) over HBM bandwidth, summed over
the decode chunks inside the trace and divided by their device time.
Moves tpot_p90_ms."""

from bench import counters, flops
from bench.serve import chunk_steps


def read(trace, record):
    if record["kind"] != "serve":
        return None
    mods = trace.modules_named("jit_chunk")
    t0 = record["window"][0]
    chunks = [c for c in record["chunks"] if c[0] >= t0]
    n = min(len(mods), len(chunks))
    if n == 0:
        return None
    cfg, pk, ps = record["cfg"], record["peaks"], record["page_size"]
    least = 0.0
    for _, _, live, budget in chunks[:n]:
        for cached in chunk_steps(live, budget, record["chunk"]):
            if cached:
                least += counters.roofline_seconds(
                    flops.decode_step_flops(cfg, cached),
                    counters.decode_step_bytes(cfg, cached, ps), pk)[0]
    device = sum(m.dur for m in mods[:n]) * 1e-9
    return 100.0 * least / device if device > 0 and least > 0 else None
