"""Roofline report (deliverable g): the dry-run three-term table per
(arch × shape × mesh), plus the *measured* kernel roofline driven through
the telemetry counter registry (``repro.telemetry.kernels``).

``run()`` emits both, writes the committed ``BENCH_roofline.json``
(per-kernel FLOPs / bytes / achieved-vs-peak on CPU smoke shapes, plus
analytic config-zoo rows), and appends a ``kernel``-kind telemetry stream
under ``benchmarks/artifacts/telemetry/`` for ``repro.telemetry.report``.
Also used to regenerate EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import fmt_row, write_bench_json
from repro.telemetry import TelemetryWriter, counters_for, zoo_cases

ARTIFACT_DIR = Path(__file__).parent / "artifacts" / "dryrun"
TELEMETRY_DIR = Path(__file__).parent / "artifacts" / "telemetry"


# --------------------------------------------------------------------------
# Dry-run cells (analytic, from committed lowering artifacts)
# --------------------------------------------------------------------------

def load_cells(mesh: str = "single") -> list:
    cells = []
    for p in sorted(ARTIFACT_DIR.glob(f"*__{mesh}.json")):
        cells.append(json.loads(p.read_text()))
    return cells


def cell_terms(d: dict) -> dict:
    from repro.launch.dryrun import roofline_terms
    return roofline_terms(d)


def table(mesh: str = "single") -> list:
    rows = []
    for d in load_cells(mesh):
        t = cell_terms(d)
        rows.append({
            "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
            "compute_s": t["compute_s"], "memory_s": t["memory_s"],
            "collective_s": t["collective_s"], "dominant": t["dominant"],
            "model_flops": t["model_flops"],
            "useful_ratio": t["useful_ratio"],
            "roofline_fraction": t["roofline_fraction"],
            "temp_gb": d["memory_analysis"].get("temp_size_in_bytes", 0) / 1e9,
            "args_gb": d["memory_analysis"].get(
                "argument_size_in_bytes", 0) / 1e9,
        })
    return rows


# --------------------------------------------------------------------------
# Measured kernel roofline (telemetry counter registry)
# --------------------------------------------------------------------------

def _best_of(fn, reps: int = 5) -> float:
    """Best wall seconds over ``reps`` post-warmup calls (compile excluded)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_peak(n: int = 512, reps: int = 5) -> dict:
    """Achievable-FLOPs anchor for this backend: best-of matmul GFLOP/s.

    Not the datasheet peak — the same-process, same-allocator rate a
    dense f32 [n,n]@[n,n] reaches, which is the honest denominator for
    "fraction of peak" on whatever machine regenerated this file.
    """
    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x, y: x @ y)
    best = _best_of(lambda: f(a, b), reps)
    flops = 2.0 * n ** 3
    return {"probe": f"matmul{n}", "gflops": flops / best / 1e9,
            "wall_us": best * 1e6}


def _smoke_cases(fast: bool = True) -> list:
    """(kernel, shape-kwargs, thunk-builder) triples at CPU smoke scale."""
    interpret = jax.default_backend() != "tpu"   # threaded, not hardcoded

    def adalomo_case(m, n):
        from repro.kernels.adalomo_update.ops import adalomo_update
        key = jax.random.PRNGKey(0)
        p = jax.random.normal(key, (m, n), jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 1), (m, n),
                              jnp.float32) * 1e-2
        r, c = jnp.ones((m,), jnp.float32), jnp.ones((n,), jnp.float32)

        def thunk():
            return adalomo_update(p, g, r, c, 1e-3, 2,
                                  interpret=interpret)

        impl = "pallas" if not interpret else "pallas_interpret"
        return ("adalomo_update", {"m": m, "n": n}, thunk, impl)

    def paged_case(batch, q_heads, kv_heads, head_dim, seq_len, page_size,
                   pages_per_seq):
        from repro.kernels.decode_attention.ops import paged_decode_attention
        key = jax.random.PRNGKey(2)
        num_pages = batch * pages_per_seq + 1
        q = jax.random.normal(key, (batch, 1, q_heads, head_dim),
                              jnp.float32)
        kp = jax.random.normal(jax.random.fold_in(key, 1),
                               (num_pages, kv_heads, page_size, head_dim),
                               jnp.float32)
        vp = jax.random.normal(jax.random.fold_in(key, 2), kp.shape,
                               jnp.float32)
        tables = (1 + jnp.arange(batch * pages_per_seq, dtype=jnp.int32)
                  ).reshape(batch, pages_per_seq)
        lens = jnp.full((batch,), seq_len, jnp.int32)
        fn = jax.jit(lambda q, kp, vp, bt, sl: paged_decode_attention(
            q, kp, vp, bt, sl, interpret=interpret))

        def thunk():
            return fn(q, kp, vp, tables, lens)

        impl = ("pallas" if jax.default_backend() == "tpu" else "jnp_ref")
        return ("paged_decode_attention",
                {"batch": batch, "q_heads": q_heads, "kv_heads": kv_heads,
                 "head_dim": head_dim, "seq_len": seq_len,
                 "page_size": page_size, "pages_per_seq": pages_per_seq},
                thunk, impl)

    cases = [adalomo_case(256, 512),
             paged_case(4, 8, 4, 64, 120, 16, 8)]
    if not fast:
        cases += [adalomo_case(1024, 1024),
                  paged_case(8, 16, 4, 64, 1000, 16, 64)]
    return cases


def measure_kernels(fast: bool = True, telemetry_path=None) -> dict:
    """Time the smoke cases through the public auto-dispatch entry points
    and pair each with its analytic counters; optionally append the rows
    to a ``kernel`` telemetry stream."""
    peak = calibrate_peak()
    writer = (TelemetryWriter(telemetry_path, stream="kernel",
                              backend=jax.default_backend())
              if telemetry_path else None)
    rows = []
    for kernel, shape, thunk, impl in _smoke_cases(fast):
        kc = counters_for(kernel, **shape)
        wall_s = _best_of(thunk, reps=3 if fast else 5)
        gflops = kc.flops / wall_s / 1e9
        row = kc.record(wall_us=wall_s * 1e6, impl=impl, gflops=gflops,
                        frac_of_peak=gflops / peak["gflops"])
        rows.append(row)
        if writer is not None:
            writer.write(row)
    if writer is not None:
        writer.close()
    analytic = [counters_for(k, **shape).record(cell=cell, analytic=True)
                for k, shape, cell in zoo_cases()]
    return {"backend": jax.default_backend(), "peak": peak,
            "kernels": rows, "analytic": analytic}


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------

def run(fast: bool = True) -> list:
    rows = []
    for mesh in ("single", "multi"):
        for r in table(mesh):
            rows.append(fmt_row(
                f"roofline/{r['arch']}/{r['shape']}/{mesh}", 0.0,
                f"compute_s={r['compute_s']:.4f};memory_s={r['memory_s']:.4f};"
                f"collective_s={r['collective_s']:.4f};dom={r['dominant']};"
                f"useful={r['useful_ratio']:.3f};"
                f"frac={r['roofline_fraction']:.3f}"))
    TELEMETRY_DIR.mkdir(parents=True, exist_ok=True)
    stream = TELEMETRY_DIR / "kernels.jsonl"
    if stream.exists():
        stream.unlink()                 # regenerate, don't append forever
    out = measure_kernels(fast, telemetry_path=stream)
    for r in out["kernels"]:
        rows.append(fmt_row(
            f"roofline/kernel/{r['kernel']}/{r['impl']}", r["wall_us"],
            f"gflops={r['gflops']:.2f};frac={r['frac_of_peak']:.4f};"
            f"intensity={r['intensity']:.2f}"))
    write_bench_json("roofline", out)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
