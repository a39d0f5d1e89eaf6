"""Model FLOP/s utilization of prefill: FLOPs of the real prompt tokens
(not the bucket's padding; logits at the last position only) over the
prefill programs' device time inside the trace and the bf16 peak.
Moves ttft_p90_ms."""

from bench import flops


def read(trace, record):
    if record["kind"] != "serve":
        return None
    mods = trace.modules_named("jit_prefill")
    t0 = record["window"][0]
    pre = [p for p in record["prefills"] if p[0] >= t0]
    n = min(len(mods), len(pre))
    if n == 0:
        return None
    f = sum(flops.prefill_flops(record["cfg"], p[2]) for p in pre[:n])
    device = sum(m.dur for m in mods[:n]) * 1e-9
    return 100.0 * f / device / record["peaks"]["bf16_flops_per_s"]
