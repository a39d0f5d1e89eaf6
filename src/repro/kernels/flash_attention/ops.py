"""Flash attention with a custom VJP over the Pallas kernels.

``flash_attention(q, k, v, q_pos, kv_pos, ...)`` takes the model's
layout (q [B,Sq,K,G,dh], k/v [B,Skv,K,dh|dv]) and positions (shared (S,)
or per row (B, S)), pads both sequences to block multiples, moves the
tensors into the kernels' tile layouts and back, and differentiates
through ``flash_bwd``.  It saves the residuals the ``jnp`` custom VJP in
``models/layers.py`` saves (q, k, v, out and the per-row log-sum-exp),
here in the kernels' layouts.  ``models.layers.attention`` chooses it on
TPU; ``interpret=True`` runs the kernels on the CPU for tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    BLOCK_KV, BLOCK_Q, flash_bwd, flash_fwd, plan)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_seq(x, n):
    """Zero-pad axis 1 of x to length n."""
    p = n - x.shape[1]
    if not p:
        return x
    return jnp.pad(x, [(0, 0), (0, p)] + [(0, 0)] * (x.ndim - 2))


def _to_tiles(x, bq):
    """[B, Sp, K, G, d] -> [B, K, nq, G·bq, d] (q tiles, heads major)."""
    B, S, K, G, d = x.shape
    nq = S // bq
    return x.reshape(B, nq, bq, K, G, d).transpose(0, 3, 1, 4, 2, 5).reshape(
        B, K, nq, G * bq, d)


def _from_tiles_t(x, G):
    """[B, K, nq, d, G·bq] (transposed tiles) -> [B, Sp, K, G, d]."""
    B, K, nq, d, R = x.shape
    bq = R // G
    return x.reshape(B, K, nq, d, G, bq).transpose(0, 2, 5, 1, 4, 3).reshape(
        B, nq * bq, K, G, d)


def _to_rows(x, bq):
    """[B, Sp, K, G] -> [B, K, nq, 1, G·bq] (per-row statistics)."""
    B, S, K, G = x.shape
    nq = S // bq
    return x.reshape(B, nq, bq, K, G).transpose(0, 3, 1, 4, 2).reshape(
        B, K, nq, 1, G * bq)


def _from_rows(x, G):
    """[B, K, nq, 1, G·bq] -> [B, Sp, K, G]."""
    B, K, nq, _, R = x.shape
    bq = R // G
    return x.reshape(B, K, nq, G, bq).transpose(0, 2, 4, 1, 3).reshape(
        B, nq * bq, K, G)


def _setup(q, k, q_pos, kv_pos, q_seg, kv_seg, causal, window, block_q,
           block_kv):
    B, Sq, K, G, _ = q.shape
    Skv = k.shape[1]
    # one block when the sequence is shorter than a block: the block then
    # spans the whole (padded) axis, which any tiling accepts
    bq = min(block_q, _round_up(Sq, 16))
    bk = min(block_kv, _round_up(Skv, 16))

    def rows(a, n):
        return None if a is None else jnp.broadcast_to(a, (B, n))

    meta = plan(rows(q_pos, Sq), rows(kv_pos, Skv), rows(q_seg, Sq),
                rows(kv_seg, Skv), G=G, bq=bq, bk=bk, causal=causal,
                window=window)
    Sp, Skvp = _round_up(Sq, bq), _round_up(Skv, bk)
    static = dict(bk=bk, causal=causal, window=window,
                  kv_len=Skv if Skvp != Skv else None)
    return meta, bq, Sp, Skvp, static


def flash_attention_lse(q, k, v, q_pos, kv_pos, *, scale, causal=True,
                        window=None, q_seg=None, kv_seg=None,
                        block_q=BLOCK_Q, block_kv=BLOCK_KV,
                        interpret=False):
    """The kernel forward: (out [B,Sq,K,G,dv], lse [B,Sq,K,G] float32)."""
    meta, bq, Sp, Skvp, static = _setup(q, k, q_pos, kv_pos, q_seg, kv_seg,
                                        causal, window, block_q, block_kv)
    out, lse, _ = _forward(q, k, v, meta, bq, Sp, Skvp, scale, static,
                           interpret)
    return out, _from_rows(lse, q.shape[3])[:, :q.shape[1]]


def _forward(q, k, v, meta, bq, Sp, Skvp, scale, static, interpret):
    Sq = q.shape[1]
    qt = _to_tiles(_pad_seq(q, Sp), bq)
    kt = _pad_seq(k, Skvp).transpose(0, 2, 1, 3)         # [B, K, Skvp, dh]
    vt = _pad_seq(v, Skvp).transpose(0, 2, 1, 3)         # [B, K, Skvp, dv]
    ot, lse = flash_fwd(qt, kt, jnp.swapaxes(vt, 2, 3), meta, scale=scale,
                        interpret=interpret, **static)
    out = _from_tiles_t(ot, q.shape[3])[:, :Sq]
    return out, lse, (qt, kt, vt)


def flash_attention(q, k, v, q_pos, kv_pos, *, scale, causal=True,
                    window=None, q_seg=None, kv_seg=None, block_q=BLOCK_Q,
                    block_kv=BLOCK_KV, interpret=False):
    """Attention of q [B,Sq,K,G,dh] over k/v [B,Skv,K,dh|dv] under the mask
    of ``layers._mask_block`` (causal; ``window``: q − k < window; equal
    segment ids where given).  Returns [B,Sq,K,G,dv] in v's dtype, and
    differentiates through the kernel backward."""
    B, Sq, K, G, dh = q.shape
    Skv = k.shape[1]
    meta, bq, Sp, Skvp, static = _setup(q, k, q_pos, kv_pos, q_seg, kv_seg,
                                        causal, window, block_q, block_kv)

    @jax.custom_vjp
    def fa(q, k, v):
        return _forward(q, k, v, meta, bq, Sp, Skvp, scale, static,
                        interpret)[0]

    def fwd(q, k, v):
        out, lse, (qt, kt, vt) = _forward(q, k, v, meta, bq, Sp, Skvp, scale,
                                          static, interpret)
        return out, (qt, kt, vt, out, lse)

    def bwd(res, dout):
        qt, kt, vt, out, lse = res
        dd = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1)                              # D: [B,Sq,K,G]
        dqt, dk, dv = flash_bwd(
            qt, _to_tiles(_pad_seq(dout, Sp), bq), kt, vt, lse,
            _to_rows(_pad_seq(dd, Sp), bq), meta, scale=scale,
            interpret=interpret, **static)
        dq = _from_tiles_t(dqt, G)[:, :Sq]
        return (dq, dk.transpose(0, 2, 1, 3)[:, :Skv],
                dv.transpose(0, 2, 1, 3)[:, :Skv])

    fa.defvjp(fwd, bwd)
    return fa(q, k, v)
