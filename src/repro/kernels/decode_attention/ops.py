"""Jitted wrappers for the flash-decoding kernels (uniform + paged)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    DEFAULT_KV_BLOCK, decode_attention_pallas, paged_decode_attention_pallas)


def decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *, scale=None,
                     window=None, kv_block=DEFAULT_KV_BLOCK,
                     interpret=False):
    """Drop-in for models.layers.decode_attention when positions are
    uniform across the batch (the serving engine's layout).

    q: [B,1,H,dh] → [B,1,H,dh]; kv_pos: [W]; q_pos: python/int scalar."""
    out = decode_attention_pallas(
        q[:, 0], k_cache, v_cache, jnp.asarray(kv_pos),
        q_pos, scale=scale, window=window, kv_block=kv_block,
        interpret=interpret)
    return out[:, None]


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           scale=None, window=None, use_kernel=None,
                           interpret=False):
    """Paged decode attention with kernel/oracle dispatch.

    The Pallas kernel streams pages through VMEM via a scalar-prefetched
    block table; the jnp path gathers pages into a contiguous cache and is
    the CPU/backstop implementation.  ``use_kernel=None`` auto-selects the
    kernel on TPU only.

    q: [B,1,H,dh]; k_pages/v_pages: [N, K, ps, dh]; block_tables: [B,P];
    seq_lens: [B] incl. the current token. Returns [B,1,H,dh]."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        out = paged_decode_attention_pallas(
            q[:, 0], k_pages, v_pages, block_tables, seq_lens,
            scale=scale, window=window, interpret=interpret)
        return out[:, None]
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    out = paged_decode_attention_ref(
        q[:, 0], k_pages, v_pages, block_tables, seq_lens,
        scale=scale, window=window)
    return out[:, None]
