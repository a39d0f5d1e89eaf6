"""Pallas TPU kernel: flash-decoding GQA attention for one query token.

The decode_32k / long_500k serving cells attend one query over a deep KV
cache.  The kernel streams KV blocks through VMEM with an online softmax —
the [W]-long score vector never materializes in HBM, and the working set
per grid step is (bw × dh) K/V tiles + (G × bw) scores, independent of W.

Grid: (B, K, W/bw) — batch × kv-head × cache blocks; inner dim fastest, so
the (m, l, acc) VMEM scratch carries across a head's cache sweep and the
output tile is written once on the last block (@pl.when).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_KV_BLOCK = 512
NEG_INF = -1e30


def _decode_kernel(scal_ref, q_ref, k_ref, v_ref, pos_ref, out_ref,
                   m_ref, l_ref, acc_ref):
    wi = pl.program_id(2)
    nw = pl.num_programs(2)
    scale, q_pos, window = scal_ref[0], scal_ref[1], scal_ref[2]

    @pl.when(wi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # [G, dh]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bw, dh]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bw, dh]
    pos = pos_ref[0, :].astype(jnp.float32)              # [bw]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G,bw]
    valid = (pos >= 0.0) & (pos <= q_pos)
    valid = valid & jnp.where(window > 0.0, q_pos - pos < window, True)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[:, 0]                                  # [G]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])                       # [G, bw]
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(wi == nw - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        out_ref[0, 0] = out.astype(out_ref.dtype)


def _paged_decode_kernel(bt_ref, sl_ref, scal_ref, q_ref, k_ref, v_ref,
                         out_ref, m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    n_pages = pl.num_programs(2)
    scale, window = scal_ref[0], scal_ref[1]
    n = sl_ref[b]                                        # tokens in cache
    ps = k_ref.shape[2]

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages wholly past the sequence end contribute nothing — skip them
    @pl.when(pi * ps < n)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)              # [G, dh]
        k = k_ref[0, 0].astype(jnp.float32)              # [ps, dh]
        v = v_ref[0, 0].astype(jnp.float32)              # [ps, dh]
        pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, ps]
        valid = pos < n                                  # [1, ps]
        valid = valid & ((window <= 0)
                         | (((n - 1) - pos).astype(jnp.float32) < window))
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                              # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                  seq_lens, *, scale=None, window=None,
                                  interpret=False):
    """Paged flash-decoding: each sequence reads its own page list.

    q: [B,H,dh]; k_pages/v_pages: [N, K, ps, dh] (page pool shared across
    sequences; one page holds ps tokens of every KV head, head-major so
    one head's (ps, dh) tile is a block the TPU compiler accepts: ps a
    multiple of 8, dh whole); block_tables: [B,P] int32
    page ids in logical order (unallocated tail entries must point at a
    valid page, e.g. the scratch page 0 — they are masked by seq_lens);
    seq_lens: [B] int32 token counts *including* the token written this
    step.  Returns [B,H,dh].

    Grid (B, K, P): the block table is scalar-prefetched so the K/V
    BlockSpec index_map can route each grid step's DMA to the right page —
    the gather never materializes a per-sequence contiguous cache.
    """
    B, H, dh = q.shape
    N, K, ps, _ = k_pages.shape
    P = block_tables.shape[1]
    G = H // K
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(B, K, G, dh)
    scal = jnp.array([scale, float(window or 0)], jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, P),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, dh), lambda b, k, p, bt, sl: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b, k, p, bt, sl: (bt[b, p], k, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b, k, p, bt, sl: (bt[b, p], k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh),
                               lambda b, k, p, bt, sl: (b, k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _paged_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      scal, qg, k_pages, v_pages)
    return out.reshape(B, H, dh)


@functools.partial(jax.jit,
                   static_argnames=("window", "kv_block", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, kv_pos, q_pos, *,
                            scale=None, window=None,
                            kv_block=DEFAULT_KV_BLOCK, interpret=False):
    """q: [B,H,dh]; caches [B,W,K,dh]; kv_pos [W] (shared across batch);
    q_pos scalar. Returns [B,H,dh]. Uniform-position batched decode —
    matches ref for kv_pos[b] identical across b (the engine's layout)."""
    B, H, dh = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else dh ** -0.5
    bw = min(kv_block, W)
    pad = (-W) % bw
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    Wp = W + pad
    assert Wp % bw == 0, f"padded window {Wp} not a multiple of {bw}"
    nw = Wp // bw

    qg = q.reshape(B, K, G, dh)
    kt = k_cache.transpose(0, 2, 1, 3)   # [B,K,W,dh]
    vt = v_cache.transpose(0, 2, 1, 3)
    pos2 = kv_pos.reshape(1, Wp)
    scal = jnp.array([scale, jnp.float32(q_pos),
                      float(window or 0)], jnp.float32)

    out = pl.pallas_call(
        _decode_kernel,
        grid=(B, K, nw),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, dh), lambda b, k, w: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, bw, dh), lambda b, k, w: (b, k, w, 0)),
            pl.BlockSpec((1, 1, bw, dh), lambda b, k, w: (b, k, w, 0)),
            pl.BlockSpec((1, bw), lambda b, k, w: (0, w)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, k, w: (b, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
        interpret=interpret,
    )(scal, qg, kt, vt, pos2)
    return out.reshape(B, H, dh)
