"""Pure-jnp oracle for the flash-attention kernels: the direct attention of
``repro.models.layers`` (one score matrix, the mask of ``_mask_block``),
with its log-sum-exp.  Gradients come from autodiff through it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def flash_attention_ref(q, k, v, q_pos, kv_pos, *, scale, causal=True,
                        window=None, q_seg=None, kv_seg=None):
    """q [B,Sq,K,G,dh]; k/v [B,Skv,K,dh|dv]; positions (S,) or (B, S).
    Returns (out [B,Sq,K,G,dv], lse [B,Sq,K,G] float32)."""
    spec = L.MaskSpec(causal=causal, window=window,
                      segmented=q_seg is not None)
    mask = L._mask_block(q_pos, kv_pos, spec, None, q_seg=q_seg,
                         kv_seg=kv_seg)
    mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    out = L._direct_attention(q, k, v, mask, scale)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    lse = jax.nn.logsumexp(jnp.where(mask, logits, L.NEG_INF), axis=-1)
    return out, lse.transpose(0, 3, 1, 2)
