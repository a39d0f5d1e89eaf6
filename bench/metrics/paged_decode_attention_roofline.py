"""Roofline share of the paged decode-attention kernel: the least time
for the live pages each row owns (⌈len/page⌉), its query and output
(bench/counters.py), over the kernel's device time in the decode chunks
inside the trace.  Moves tpot_p90_ms."""

from bench import counters
from bench.serve import chunk_steps

KERNEL = r"paged_decode_attention"


def read(trace, record):
    if record["kind"] != "serve":
        return None
    mods = trace.modules_named("jit_chunk")
    t0 = record["window"][0]
    chunks = [c for c in record["chunks"] if c[0] >= t0]
    n = min(len(mods), len(chunks))
    if n == 0:
        return None
    t = sum(trace.ops_in(m, KERNEL) for m in mods[:n])
    cfg, ps = record["cfg"], record["page_size"]
    fl = by = 0
    for _, _, live, budget in chunks[:n]:
        for cached in chunk_steps(live, budget, record["chunk"]):
            if cached:
                w = counters.paged_attention_work(cfg, cached, ps)
                fl, by = fl + w["flops"], by + w["bytes"]
    if t <= 0 or by <= 0:
        return None
    least, _ = counters.roofline_seconds(fl, by, record["peaks"])
    return 100.0 * least / t
