"""On-chip smoke test: the fused AdaLomo training step and paged serving,
through their normal entry points, at the full width of h2o-danube-1.8b.

  python chip_smoke.py              # one TPU chip: kernels, train, serve
  python chip_smoke.py --chips 4    # four chips: the 2x2-mesh train step
                                    # against the same spec on one chip

Phases run in order in this one process (a chip belongs to one process at
a time) and the script exits non-zero at the first fault.  It never falls
back to the CPU: without a TPU it exits non-zero before any phase.  The
timings it prints are smoke timings of one short run, not benchmark
results.  The last line of stdout is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Weights are random (seeded) and the training data is the repo's seeded
synthetic pipeline.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

ARCH = "h2o-danube-1.8b"
SEQ, BATCH, STEPS = 4096, 4, 6
SEED = 0
N_REQUESTS, NEW_TOKENS, PROMPT_RANGE = 8, 32, (64, 1024)
# mesh vs one chip: the losses of one 2x2-mesh run and one single-chip run
# differed by at most 5.1e-4 on a v5e (the whole 6-step curve moves ~0.07)
MESH_LOSS_ATOL = 2e-3
# and the norm of each parameter's 6-step change, relative to one chip's.
# Δθ of a bf16 parameter is whole ulps on the elements that flipped, so the
# bound holds for parameters with at least MESH_MIN_CHANGED flips: below
# that, which elements sit next to a rounding boundary decides the norm
MESH_CHANGE_RTOL = 2e-2
MESH_MIN_CHANGED = 10_000
# whole model |Δ_mesh − Δ_one| / |Δ_one|: ≈ √ε for runs whose updates
# differ by a relative ε (the two sides of a rounding boundary); a dropped
# update reads 1, an update written to the wrong shards ≈ √2
MESH_CHANGE_GAP = 0.5


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def tpu_devices():
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: JAX could not start a backend ({e})")
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devices[0].platform!r}")
    return devices


def mem_gib(device, key: str = "peak_bytes_in_use") -> float:
    stats = device.memory_stats() or {}
    return stats.get(key, float("nan")) / 2 ** 30


# --------------------------------------------------------------------------
# Phase: kernels on the chip vs their jnp references
# --------------------------------------------------------------------------

def adalomo_case(dtype, tol):
    """adalomo_update at the danube MLP width (several row and column
    blocks) on the chip vs ref.py; returns (θ, kernel θ', ref θ')."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.adalomo_update.ops import adalomo_update
    from repro.kernels.adalomo_update.ref import adalomo_update_ref

    m, n, lr, step = 2560, 6912, 5e-4, 5.0
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    p = (jax.random.normal(ks[0], (m, n)) * 0.1).astype(dtype)
    g = (jax.random.normal(ks[1], (m, n)) * 0.3).astype(dtype)
    r = jax.random.uniform(ks[2], (m,)) * 1e-2
    c = jax.random.uniform(ks[3], (n,)) * 1e-2
    pr, rr, cr = jax.jit(lambda p, g, r, c: adalomo_update_ref(
        p, g, r, c, lr=lr, step=step))(p, g, r, c)
    t0 = time.perf_counter()
    pk, rk, ck = jax.block_until_ready(adalomo_update(p, g, r, c, lr, step))
    name = jnp.dtype(dtype).name
    log(f"[kernels] adalomo_update {m}x{n} {name}: first call "
        f"{time.perf_counter() - t0:.2f} s (incl. compile)")
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(pk), f32(pr), rtol=tol, atol=tol)
    np.testing.assert_allclose(rk, rr, rtol=3e-5, atol=1e-5)
    np.testing.assert_allclose(ck, cr, rtol=3e-5, atol=1e-5)
    log(f"[kernels] adalomo_update {name} matches ref.py")
    return f32(p), f32(pk), f32(pr)


def kernels_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.registry import get_arch

    adalomo_case(jnp.bfloat16, 5e-3)
    # In bf16 most of Δθ = θ' − θ (~lr·RMS(θ) ≈ 5e-5) rounds away below
    # half an ulp of θ, so the update itself — the RMS(u)/RMS(θ) scales
    # accumulated across blocks — is checked in f32.
    p, pk, pr = adalomo_case(jnp.float32, 1e-5)
    dk, dr = pk - p, pr - p
    rel = float(np.linalg.norm(dk - dr) / np.linalg.norm(dr))
    log(f"[kernels] adalomo_update f32 update: |dθ_kernel - dθ_ref| / "
        f"|dθ_ref| = {rel:.3e}, |dθ_ref| / |θ| = "
        f"{np.linalg.norm(dr) / np.linalg.norm(p):.3e}")
    check(rel < 1e-3, f"adalomo_update's update differs from ref.py by "
                      f"{rel:.3e} of its norm")

    # paged_decode_attention at danube serving widths: ragged lengths,
    # shuffled block tables, junk in every page no sequence owns
    cfg = get_arch(ARCH).cfg
    H, K, dh, ps, P, B = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16,
                          64, 8)
    rng = np.random.RandomState(SEED)
    N = 1 + B * P
    bt = rng.permutation(np.arange(1, N)).reshape(B, P).astype(np.int32)
    lens = rng.randint(1, P * ps + 1, size=B).astype(np.int32)
    lens[0], lens[1] = 1, P * ps            # both ends of the range
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh)).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (N, K, ps, dh)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (N, K, ps, dh)).astype(jnp.bfloat16)
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)
    ref = jax.jit(lambda *a: paged_decode_attention_ref(
        *a, window=cfg.window))(q[:, 0], kp, vp, bt, lens)
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, window=cfg.window, use_kernel=True))
    check("tpu_custom_call" in fn.lower(q, kp, vp, bt, lens).as_text(),
          "paged decode attention did not lower to the Pallas kernel")
    out = fn(q, kp, vp, bt, lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
    log(f"[kernels] paged_decode_attention H={H} K={K} dh={dh} ps={ps} "
        f"matches ref.py")

    # flash attention, forward and backward, at danube training widths;
    # S off the block grid, so the last blocks pad
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    B, S, G = 2, 2304, H // K
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, dh)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, dh)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, dh)).astype(jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, K, G, dh)).astype(jnp.bfloat16)
    pos = jnp.arange(S, dtype=jnp.int32)

    def fwd_bwd(attn):
        def f(q, k, v, do):
            out, pullback = jax.vjp(attn, q, k, v)
            return (out,) + pullback(do)
        return jax.jit(f)

    kw = dict(scale=dh ** -0.5, causal=True, window=cfg.window)
    got = fwd_bwd(lambda q, k, v: flash_attention(q, k, v, pos, pos, **kw))(
        q, k, v, do)
    want = fwd_bwd(lambda q, k, v: flash_attention_ref(
        q, k, v, pos, pos, **kw)[0])(q, k, v, do)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        check(rel < 3e-2, f"flash attention {name} differs from ref.py by "
                          f"{rel:.3e} of its largest element")
    log(f"[kernels] flash_attention B={B} S={S} H={H} K={K} dh={dh} "
        f"forward and backward match ref.py")


# --------------------------------------------------------------------------
# Phase: training through run()
# --------------------------------------------------------------------------

def train_spec(mesh_shape=None):
    from repro.data.pipeline import DataConfig
    from repro.run import ModelSpec, OptSpec, RunSpec, StepSpec
    from repro.run.spec import FaultSpec, MeshSpec
    return RunSpec(
        model=ModelSpec(ARCH),
        data=DataConfig(vocab=0, seq_len=SEQ, global_batch=BATCH),
        opt=OptSpec(name="adalomo"),
        steps=StepSpec(total=STEPS),
        seed=SEED,
        mesh=(MeshSpec(kind="multi", shape=mesh_shape) if mesh_shape
              else MeshSpec()),
        # a device error fails the smoke: no restore-and-retry
        fault=FaultSpec(retries=0))


def run_train(spec, tag: str):
    from repro.run import run
    from repro.run.hooks import Hook

    class StepTimes(Hook):
        def __init__(self):
            self.dt = []

        def on_step_end(self, ctx, ev):
            self.dt.append(ev.dt)

    times = StepTimes()
    t0 = time.perf_counter()
    result = run(spec, hooks=(times,), log_fn=lambda s: log(f"[{tag}] {s}"))
    wall = time.perf_counter() - t0
    losses = result.history["loss"]
    check(len(losses) == STEPS, f"[{tag}] ran {len(losses)} of {STEPS} steps")
    check(all(math.isfinite(x) for x in losses),
          f"[{tag}] non-finite loss: {losses}")
    check(result.program.cache_size() == 1,
          f"[{tag}] step compiled {result.program.cache_size()} times")
    med = statistics.median(times.dt[1:])
    log(f"[{tag}] losses {[round(x, 4) for x in losses]}")
    log(f"[{tag}] smoke timings: run {wall:.1f} s, first step "
        f"{times.dt[0]:.1f} s (incl. compile), median step "
        f"{med * 1e3:.1f} ms, {BATCH * SEQ / med:.0f} tokens/s")
    return result, losses


def train_phase() -> None:
    import jax
    result, _ = run_train(train_spec(), "train")
    text = result.program.lower().as_text()
    check("tpu_custom_call" in text,
          "the training step did not lower to the AdaLomo kernel")
    log(f"[train] step holds the AdaLomo kernel; peak_bytes_in_use "
        f"{mem_gib(jax.devices()[0]):.2f} GiB")


# --------------------------------------------------------------------------
# Phase: paged serving
# --------------------------------------------------------------------------

def serve_phase() -> None:
    import jax
    import numpy as np

    from repro.models.registry import get_arch
    from repro.serve.engine import PagedEngine, PagedServeConfig
    from repro.serve.paging import pages_for
    from repro.serve.scheduler import FINISHED

    log(f"[serve] bytes_in_use at start "
        f"{mem_gib(jax.devices()[0], 'bytes_in_use'):.2f} GiB")
    arch = get_arch(ARCH)
    params = arch.init_params(jax.random.PRNGKey(SEED))
    rng = np.random.RandomState(SEED)
    lens = rng.randint(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1, N_REQUESTS)
    lens[0], lens[1] = PROMPT_RANGE
    prompts = [rng.randint(0, arch.cfg.vocab, n).tolist() for n in lens]
    ps = 16
    per_seq = pages_for(PROMPT_RANGE[1] + NEW_TOKENS, ps)
    scfg = PagedServeConfig(page_size=ps, num_pages=1 + N_REQUESTS * per_seq,
                            max_batch=N_REQUESTS, max_pages_per_seq=per_seq,
                            chunk=8, max_new_tokens=NEW_TOKENS, seed=SEED)
    engine = PagedEngine(arch, params, scfg)
    free0 = engine.allocator.n_free

    t0 = time.perf_counter()
    engine.warmup([int(n) for n in lens])
    log(f"[serve] warmup (prefill buckets + decode chunk) "
        f"{time.perf_counter() - t0:.1f} s (compile)")
    check("tpu_custom_call" in engine.lower_decode_chunk().as_text(),
          "the decode chunk did not lower to the paged attention kernel")

    rids = [engine.submit(p) for p in prompts]
    step_s = []
    t0 = time.perf_counter()
    while engine.scheduler.has_work():
        ts = time.perf_counter()
        engine.step()
        step_s.append(time.perf_counter() - ts)
    wall = time.perf_counter() - t0

    reqs = [engine.requests[r] for r in rids]
    check(all(r.status == FINISHED for r in reqs),
          f"unfinished requests: {[r.status for r in reqs]}")
    check(all(len(r.out) == NEW_TOKENS for r in reqs),
          f"token counts {[len(r.out) for r in reqs]} != {NEW_TOKENS}")
    check(all(0 <= t < arch.cfg.vocab for r in reqs for t in r.out),
          "generated token id outside the vocabulary")
    check(engine.allocator.n_free == free0,
          f"page leak: {engine.allocator.n_free} free, started {free0}")
    check(engine.decode_compile_count() == 1,
          f"decode chunk compiled {engine.decode_compile_count()} times")
    n_tok = N_REQUESTS * NEW_TOKENS
    # round 1 admits (prefills) every request; the rest are decode chunks
    decode_ms = statistics.median(step_s[1:]) * 1e3
    log(f"[serve] {N_REQUESTS} requests, prompts {sorted(lens.tolist())}, "
        f"{NEW_TOKENS} new tokens each")
    log(f"[serve] smoke timings: {wall:.2f} s, {n_tok / wall:.1f} "
        f"tokens/s, first round (prefill + chunk) {step_s[0] * 1e3:.1f} "
        f"ms, decode {decode_ms:.1f} ms per chunk of {scfg.chunk}; "
        f"peak_bytes_in_use {mem_gib(jax.devices()[0]):.2f} GiB")


# --------------------------------------------------------------------------
# Four chips: the 2x2-mesh train step against one chip
# --------------------------------------------------------------------------

def param_change_readings(theta0, one, four):
    """How the 6-step parameter change of the mesh run compares with the
    one-chip run's, on the host.  Returns the worst per-parameter relative
    gap between the norms of the two changes, over the parameters with at
    least MESH_MIN_CHANGED changed elements and over the rest, each as
    (gap, path, changed elements), and the whole model's
    |Δ_mesh − Δ_one| / |Δ_one|."""
    import jax
    import numpy as np

    held, rest, gap2, ref2 = (0.0, "", 0), (0.0, "", 0), 0.0, 0.0
    for (path, a0), a1, a4 in zip(jax.tree_util.tree_leaves_with_path(theta0),
                                  jax.tree_util.tree_leaves(one),
                                  jax.tree_util.tree_leaves(four)):
        a0 = np.asarray(a0, np.float32)
        d1 = np.asarray(a1, np.float32) - a0
        d4 = np.asarray(a4, np.float32) - a0
        n1, n4 = float(np.linalg.norm(d1)), float(np.linalg.norm(d4))
        rel = abs(n4 - n1) / n1 if n1 else (0.0 if n4 == 0 else math.inf)
        reading = (rel, jax.tree_util.keystr(path), int(np.count_nonzero(d1)))
        if reading[2] >= MESH_MIN_CHANGED:
            held = max(held, reading)
        else:
            rest = max(rest, reading)
        gap2 += float(np.sum(np.square(d4 - d1)))
        ref2 += n1 ** 2
    return held, rest, math.sqrt(gap2 / ref2)


def mesh_phase(devices) -> None:
    import re

    import jax
    import numpy as np

    from repro.models.registry import get_arch

    spec = train_spec()
    # run() initialises from spec.seed; keep that θ0 on the host
    theta0 = jax.device_get(get_arch(spec.model.arch).init_params(
        jax.random.PRNGKey(spec.seed)))
    result, one = run_train(spec, "train 1 chip")
    one_params = jax.device_get(result.params)
    del result
    gc.collect()
    result, four = run_train(train_spec((2, 2)), "train 2x2 mesh")
    gap = max(abs(a - b) for a, b in zip(four, one))
    log(f"[mesh] losses: max |mesh - one chip| {gap:.3e} "
        f"(limit {MESH_LOSS_ATOL:g})")
    np.testing.assert_allclose(four, one, rtol=0, atol=MESH_LOSS_ATOL)
    for path, leaf in jax.tree_util.tree_leaves_with_path(result.params):
        check(len(leaf.sharding.device_set) == len(devices),
              f"{jax.tree_util.keystr(path)} lives on "
              f"{len(leaf.sharding.device_set)} devices")
    log(f"[mesh] every parameter has shards on all {len(devices)} devices")
    held, rest, whole = param_change_readings(
        theta0, one_params, jax.device_get(result.params))
    log(f"[mesh] parameter change after {STEPS} steps, "
        f"| |d_mesh| - |d_one| | / |d_one|: worst {held[0]:.3e} at "
        f"{held[1]} ({held[2]} changed elements; limit "
        f"{MESH_CHANGE_RTOL:g}); worst of parameters with fewer than "
        f"{MESH_MIN_CHANGED} changed elements {rest[0]:.3e} at {rest[1]} "
        f"({rest[2]})")
    log(f"[mesh] whole model |d_mesh - d_one| / |d_one| {whole:.3e} "
        f"(limit {MESH_CHANGE_GAP:g})")
    check(held[0] < MESH_CHANGE_RTOL,
          f"the mesh step's change of {held[1]} differs from one chip's by "
          f"{held[0]:.3e} of its norm")
    check(whole < MESH_CHANGE_GAP,
          f"the mesh step's parameter change differs from one chip's by "
          f"{whole:.3e} of its norm")
    text = result.program.lower().compile().as_text()
    check("tpu_custom_call" in text, "the mesh step lost the kernel")
    gathers = re.findall(r"(\w+\[[\d,]*\])\S* all-gather(?:-start)?\(",
                         text)
    big = sorted({s for s in gathers if s.startswith("bf16[")})
    log(f"[mesh] compiled step: {len(gathers)} all-gathers; bf16 "
        f"all-gather shapes {big}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 2x2-mesh train step against "
                         "one chip")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but {len(devices)} TPU device(s) visible")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    if args.chips == 4:
        mesh_phase(devices[:4])
    else:
        kernels_phase()
        train_phase()
        gc.collect()
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
