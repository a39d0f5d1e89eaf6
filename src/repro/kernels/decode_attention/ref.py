"""Pure-jnp oracle for the flash-decoding kernel: exactly
repro.models.layers.decode_attention (the serving path's attention)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers import decode_attention


def decode_attention_ref(q, k_cache, v_cache, *, kv_pos, q_pos,
                         window=None, scale=None):
    """q: [B,H,dh]; caches: [B,W,K,dh]; kv_pos: [B,W]; q_pos: [B].
    Returns [B,H,dh]."""
    out = decode_attention(q[:, None], k_cache, v_cache, kv_pos=kv_pos,
                           q_pos=q_pos, window=window, scale=scale)
    return out[:, 0]


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, seq_lens,
                               *, window=None, scale=None):
    """Dense oracle for the paged kernel: gather each sequence's pages into
    a contiguous cache and run the exact serving-path attention.

    q: [B,H,dh]; k_pages/v_pages: [N, K, ps, dh]; block_tables: [B,P];
    seq_lens: [B] (counts include the current token). Returns [B,H,dh]."""
    B = q.shape[0]
    _, K, ps, dh = k_pages.shape
    P = block_tables.shape[1]

    def gather(pages):   # [B,P,K,ps,dh] -> [B, P*ps, K, dh]
        return pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
            B, P * ps, K, dh)

    kc, vc = gather(k_pages), gather(v_pages)
    pos = jnp.arange(P * ps, dtype=jnp.int32)
    kv_pos = jnp.where(pos[None, :] < seq_lens[:, None], pos[None, :], -1)
    q_pos = jnp.maximum(seq_lens - 1, 0).astype(jnp.int32)
    out = decode_attention(q[:, None], kc, vc, kv_pos=kv_pos, q_pos=q_pos,
                           window=window, scale=scale)
    return out[:, 0]
