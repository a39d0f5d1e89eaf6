"""Shared neural-net layers for the model zoo (pure JAX, scan-friendly).

Everything here is a pure function over explicit parameter pytrees so that
layers compose with the fused-backward engine (``core/fused.py``) and shard
cleanly under pjit.  Attention supports GQA/MQA, sliding windows (SWA),
qk-norm, prefix-LM masks and cross-attention, with a two-level blockwise
(flash-style) path for long sequences that never materializes an S×S score
matrix.  On a TPU that path runs as the Pallas flash-attention kernels
(``kernels/flash_attention``) wherever their masks cover it: on one device,
and under a mesh whose tensor-parallel axis divides the kv heads, each
device on its own heads.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding.act import shard_act

Array = jax.Array

# Sequences at or below this use the direct einsum attention path; above it,
# the blockwise online-softmax path (bounded memory, compile-friendly scans).
# 2048 keeps the S×S score tensor out of HBM at the train_4k production
# shape (§Perf H3); tests/decode paths pass force_direct explicitly.
_DIRECT_ATTN_MAX_SEQ = 2048
_Q_BLOCK = 1024
_KV_BLOCK = 1024

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layernorm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def norm_apply(params: dict, x: Array, *, kind: str, eps: float = 1e-6) -> Array:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], eps)
    return layernorm(x, params["scale"], params["bias"], eps)


def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        # stored as (scale - 1) so zeros-init == identity; see rmsnorm().
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_sincos(positions: Array, d_rot: int, theta: float = 10000.0
                ) -> tuple[Array, Array]:
    """positions: (...,) int -> sin/cos tables (..., d_rot/2) fp32."""
    half = d_rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: Array, sin: Array, cos: Array, rope_pct: float = 1.0
               ) -> Array:
    """x: (..., S, H, dh); sin/cos: (S, d_rot/2) or broadcastable."""
    dh = x.shape[-1]
    d_rot = int(dh * rope_pct)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    # sin/cos broadcast over batch & head dims: (S, half) -> (S, 1, half)
    s = sin[..., :, None, :]
    c = cos[..., :, None, :]
    rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------------------
# Masks (computed from positions on the fly — never S×S in HBM for long S)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MaskSpec:
    causal: bool = True
    window: Optional[int] = None       # SWA: attend to [pos-window+1, pos]
    # prefix-LM: kv positions < prefix_len[b] are visible to every query
    has_prefix: bool = False
    # packed-segment batches: attention also requires equal segment ids
    # (q_seg/kv_seg arrays travel alongside positions); incompatible with
    # has_prefix.  Static at trace time like every other MaskSpec field.
    segmented: bool = False


def _mask_block(q_pos: Array, kv_pos: Array, spec: MaskSpec,
                prefix_len: Optional[Array], q_seg: Optional[Array] = None,
                kv_seg: Optional[Array] = None) -> Array:
    """Bool mask block (..., Sq, Skv) from position (and segment) vectors."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], kv_pos.shape[-1]),
                 dtype=bool)
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    if spec.causal:
        m = m & (q >= k)
    if spec.window is not None:
        m = m & (q - k < spec.window)
    if q_seg is not None:
        m = m & (q_seg[..., :, None] == kv_seg[..., None, :])
    if spec.has_prefix and prefix_len is not None:
        pl = prefix_len.reshape(prefix_len.shape + (1, 1))
        m = m | (k < pl)
        if spec.window is not None:
            m = m & ((q - k < spec.window) | (k < pl))
    return m


def _scan_block_mask(qp: Array, kp: Array, qs: Optional[Array],
                     ks: Optional[Array], spec: MaskSpec,
                     pl4: Optional[Array]) -> Array:
    """Mask for one (q_block, kv_block) pair inside the blockwise scans.

    qp: (T, qb) tile-shared metadata or (B, T, qb) per-row (packed
    segments); kp: (kb,) or (B, kb) correspondingly; qs/ks: segment-id
    blocks of the same shapes, or None.  Returns a mask broadcastable
    against score blocks [B, T, K, G, qb, kb]: leading dim 1 when the
    metadata is row-invariant, B otherwise.
    """
    batched = qp.ndim == 3
    qe = qp[..., :, None]                           # (T,qb,1) | (B,T,qb,1)
    ke = kp[:, None, None, :] if batched else kp[None, None, :]
    m = jnp.ones(jnp.broadcast_shapes(qe.shape, ke.shape), bool)
    if spec.causal:
        m = m & (qe >= ke)
    if spec.window is not None:
        m = m & (qe - ke < spec.window)
    if qs is not None:
        kse = ks[:, None, None, :] if batched else ks[None, None, :]
        m = m & (qs[..., :, None] == kse)
    if spec.has_prefix and pl4 is not None:
        # prefix-LM is unpacked-only (1-D metadata): lift to (B,T,qb,kb)
        m = m[None] | (ke[None] < pl4)
        if spec.window is not None:
            m = m & ((qe - ke < spec.window)[None] | (ke[None] < pl4))
        return m[:, :, None, None]                  # (B,T,1,1,qb,kb)
    if batched:
        return m[:, :, None, None]                  # (B,T,1,1,qb,kb)
    return m[None, :, None, None]                   # (1,T,1,1,qb,kb)


def _q_meta_blocks(a: Array, T: int, Sloc: int, pq: int, qb: int,
                   fill) -> Array:
    """Tile + pad + block query metadata (positions / segment ids):
    (Sq,) -> [nq, T, qb]; (B, Sq) -> [nq, B, T, qb]."""
    a = a.reshape(a.shape[:-1] + (T, Sloc))
    if pq:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pq)],
                    constant_values=fill)
    nq = (Sloc + pq) // qb
    a = a.reshape(a.shape[:-1] + (nq, qb))
    if a.ndim == 3:
        return a.transpose(1, 0, 2)
    return a.transpose(2, 0, 1, 3)


def _kv_meta_blocks(a: Array, pk: int, kb: int, fill) -> Array:
    """Pad + block kv metadata: (Skv,) -> [nk, kb]; (B, Skv) -> [nk, B, kb]."""
    if pk:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pk)],
                    constant_values=fill)
    nk = a.shape[-1] // kb
    a = a.reshape(a.shape[:-1] + (nk, kb))
    return a if a.ndim == 2 else a.transpose(1, 0, 2)


# Fill values for padded metadata slots: a padded query (pos -1, seg -1)
# and a padded kv (pos 2**30, seg -2) can never satisfy causal/window or
# segment-equality terms against any real slot.
_QPOS_FILL, _KPOS_FILL = -1, 2 ** 30
_QSEG_FILL, _KSEG_FILL = -1, -2


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def _direct_attention(q, k, v, mask, scale):
    """q: [B,Sq,K,G,dh] k/v: [B,Skv,K,dh] mask: broadcastable [B,1,1,Sq,Skv]."""
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out


def _block_attention(q, k, v, q_pos, kv_pos, spec, prefix_len, scale,
                     q_block: int, kv_block: int, tiles: int = 1,
                     return_lse: bool = False, q_seg=None, kv_seg=None):
    """Two-level blockwise attention with online softmax (flash-style).

    q: [B,Sq,K,G,dh]; k/v: [B,Skv,K,dh]; q_pos: (Sq,) shared across rows,
    or (B,Sq) per-row for packed-segment batches (then q_seg/kv_seg carry
    matching segment ids and attention never crosses a segment).
    Scans query blocks (outer) and KV blocks (inner); score blocks of shape
    [B,T,K,G,qb,kb] are the only O(S·block) intermediates.

    ``tiles`` > 1 enables *sequence-tiled* execution (§Perf): the query
    sequence is split into T tiles carried as a tensor dim sharded over the
    model axis, so the q-block scan axis stays unsharded — every device
    processes its own S/T query rows each step (context parallelism in
    plain pjit, no shard_map).
    """
    B, Sq, K, G, dh = q.shape
    dv = v.shape[-1]
    Skv = k.shape[1]
    if q_seg is not None and q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos, (B, Sq))
        kv_pos = jnp.broadcast_to(kv_pos, (B, Skv))
    T = tiles if (tiles > 1 and Sq % tiles == 0) else 1
    Sloc = Sq // T
    qb = min(q_block, Sloc)
    kb = min(kv_block, Skv)
    # pad local q length and kv to block multiples
    pq = (-Sloc) % qb
    pk = (-Skv) % kb
    # metadata (positions / segment ids) -> padded per-tile blocks; fills
    # chosen so padded slots can never pass the mask against real slots
    qps = _q_meta_blocks(q_pos, T, Sloc, pq, qb, _QPOS_FILL)
    qss = (_q_meta_blocks(q_seg, T, Sloc, pq, qb, _QSEG_FILL)
           if q_seg is not None else None)
    kps = _kv_meta_blocks(kv_pos, pk, kb, _KPOS_FILL)
    kss = (_kv_meta_blocks(kv_seg, pk, kb, _KSEG_FILL)
           if kv_seg is not None else None)
    seg = qss is not None
    if pq:  # pad within each tile: reshape → pad → flatten
        q = q.reshape(B, T, Sloc, K, G, dh)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q = q.reshape(B, T * (Sloc + pq), K, G, dh)
        Sloc += pq
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = Sloc // qb, k.shape[1] // kb

    # [nq, B, T, qb, K, G, dh]; the T dim carries the tp sharding
    qs = shard_act(q.reshape(B, T, nq, qb, K, G, dh), "q_tiled"
                   ).transpose(2, 0, 1, 3, 4, 5, 6)
    ks = k.reshape(B, nk, kb, K, dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kb, K, dv).transpose(1, 0, 2, 3, 4)

    pl4 = (prefix_len.reshape(B, 1, 1, 1)
           if prefix_len is not None else None)

    def q_step(_, q_in):
        if seg:
            qi, qp, qsg = q_in  # [B,T,qb,K,G,dh], (T,qb)|(B,T,qb), seg ids
        else:
            (qi, qp), qsg = q_in, None

        def kv_step(carry, kv_in):
            m_run, l_run, acc = carry
            if seg:
                ki, vi, kp, ksg = kv_in
            else:
                (ki, vi, kp), ksg = kv_in, None
            logits = jnp.einsum("btqkgd,bskd->btkgqs", qi, ki,
                                preferred_element_type=jnp.float32) * scale
            mask = _scan_block_mask(qp, kp, qsg, ksg, spec, pl4)
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("btkgqs,bskd->btkgqd", p.astype(vi.dtype), vi)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, T, K, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, T, K, G, qb), jnp.float32)
        a0 = jnp.zeros((B, T, K, G, qb, dv), jnp.float32)
        kv_xs = (ks, vs, kps, kss) if seg else (ks, vs, kps)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kv_xs)
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        out = out.astype(v.dtype)
        lse = m_f + jnp.log(jnp.maximum(l_f, 1e-30))  # [B,T,K,G,qb]
        return None, (out.transpose(0, 1, 4, 2, 3, 5),  # [B,T,qb,K,G,dv]
                      lse.transpose(0, 1, 4, 2, 3))     # [B,T,qb,K,G]

    q_xs = (qs, qps, qss) if seg else (qs, qps)
    _, (outs, lses) = jax.lax.scan(q_step, None, q_xs)
    out = outs.transpose(1, 2, 0, 3, 4, 5, 6).reshape(
        B, T * nq * qb, K, G, dv)
    lse = lses.transpose(1, 2, 0, 3, 4, 5).reshape(B, T * nq * qb, K, G)
    if pq:
        out = out.reshape(B, T, Sloc, K, G, dv)[:, :, :Sloc - pq].reshape(
            B, Sq, K, G, dv)
        lse = lse.reshape(B, T, Sloc, K, G)[:, :, :Sloc - pq].reshape(
            B, Sq, K, G)
    if return_lse:
        return out, lse
    return out


def _flash_attention(q, k, v, q_pos, kv_pos, spec, prefix_len, scale,
                     q_block: int, kv_block: int, tiles: int,
                     q_seg=None, kv_seg=None):
    """Blockwise attention with a flash-style custom VJP (``attention()``
    takes the Pallas kernels instead on a TPU, where their masks reach).

    Differentiating through the online-softmax scan makes jax save every
    per-block softmax intermediate — stacked [nk, B, T, K, G, qb, kb] fp32
    tensors that dominated the qwen3 train cell's memory term (§Perf H5).
    The custom VJP saves only (q, k, v, out, lse) and *recomputes* the
    probabilities blockwise in the backward pass, exactly like
    FlashAttention's backward.  Segment masking (packed batches) is part
    of the recomputed mask, so the backward drops cross-segment terms the
    same way the forward does.
    """
    if q_seg is not None and q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos, (q.shape[0], q.shape[1]))
        kv_pos = jnp.broadcast_to(kv_pos, (k.shape[0], k.shape[1]))

    @jax.custom_vjp
    def fa(q, k, v):
        return _block_attention(q, k, v, q_pos, kv_pos, spec, prefix_len,
                                scale, q_block, kv_block, tiles,
                                q_seg=q_seg, kv_seg=kv_seg)

    def fwd(q, k, v):
        out, lse = _block_attention(q, k, v, q_pos, kv_pos, spec,
                                    prefix_len, scale, q_block, kv_block,
                                    tiles, return_lse=True,
                                    q_seg=q_seg, kv_seg=kv_seg)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        B, Sq, K, G, dh = q.shape
        dvd = v.shape[-1]
        Skv = k.shape[1]
        T = tiles if (tiles > 1 and Sq % tiles == 0) else 1
        Sloc = Sq // T
        qb = min(q_block, Sloc)
        kb = min(kv_block, Skv)
        pq = (-Sloc) % qb
        pk = (-Skv) % kb
        seg = q_seg is not None
        D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [B,Sq,K,G]

        def pad_q(x, fill=0.0):
            x = x.reshape((B, T, Sloc) + x.shape[2:])
            if pq:
                pad = [(0, 0), (0, 0), (0, pq)] + [(0, 0)] * (x.ndim - 3)
                x = jnp.pad(x, pad, constant_values=fill)
            return x

        qt = pad_q(q)
        dot_ = pad_q(dout)
        lset = pad_q(lse, fill=0.0)
        Dt = pad_q(D)
        qps = _q_meta_blocks(q_pos, T, Sloc, pq, qb, _QPOS_FILL)
        qss = (_q_meta_blocks(q_seg, T, Sloc, pq, qb, _QSEG_FILL)
               if seg else None)
        kps = _kv_meta_blocks(kv_pos, pk, kb, _KPOS_FILL)
        kss = (_kv_meta_blocks(kv_seg, pk, kb, _KSEG_FILL)
               if seg else None)
        Slp = Sloc + pq
        nq = Slp // qb
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        nk = k.shape[1] // kb

        # [nq, B, T, qb, ...] blocks
        def blk(x):
            return x.reshape((B, T, nq, qb) + x.shape[3:]).transpose(
                (2, 0, 1, 3) + tuple(range(4, x.ndim + 1)))

        qs, dos = blk(qt), blk(dot_)
        lses, Ds = blk(lset), blk(Dt)
        ks = k.reshape(B, nk, kb, K, dh).transpose(1, 0, 2, 3, 4)
        vs = v.reshape(B, nk, kb, K, dvd).transpose(1, 0, 2, 3, 4)
        pl4 = (prefix_len.reshape(B, 1, 1, 1)
               if prefix_len is not None else None)

        def q_step(carry, xs):
            dk_acc, dv_acc = carry  # [nk,B,kb,K,dh/dv] fp32
            if seg:
                qi, doi, lsei, Di, qp, qsg = xs
            else:
                (qi, doi, lsei, Di, qp), qsg = xs, None
            # btkgq layouts for lse/D
            lse_t = lsei.transpose(0, 1, 3, 4, 2)  # [B,T,K,G,qb]
            D_t = Di.transpose(0, 1, 3, 4, 2)

            def kv_step(dq_acc, xs2):
                if seg:
                    ki, vi, kp, ksg = xs2
                else:
                    (ki, vi, kp), ksg = xs2, None
                logits = jnp.einsum(
                    "btqkgd,bskd->btkgqs", qi, ki,
                    preferred_element_type=jnp.float32) * scale
                maskb = _scan_block_mask(qp, kp, qsg, ksg, spec, pl4)
                p = jnp.where(maskb,
                              jnp.exp(logits - lse_t[..., None]), 0.0)
                dv_b = jnp.einsum("btkgqs,btqkgv->bskv", p,
                                  doi.astype(jnp.float32))
                dp = jnp.einsum("btqkgv,bskv->btkgqs",
                                doi.astype(jnp.float32),
                                vi.astype(jnp.float32))
                ds = p * (dp - D_t[..., None])
                dq_b = jnp.einsum("btkgqs,bskd->btqkgd", ds,
                                  ki.astype(jnp.float32)) * scale
                dk_b = jnp.einsum("btkgqs,btqkgd->bskd", ds,
                                  qi.astype(jnp.float32)) * scale
                return dq_acc + dq_b, (dk_b, dv_b)

            dq0 = jnp.zeros(qi.shape, jnp.float32)
            kv_xs = (ks, vs, kps, kss) if seg else (ks, vs, kps)
            dq_i, (dk_js, dv_js) = jax.lax.scan(kv_step, dq0, kv_xs)
            return (dk_acc + dk_js, dv_acc + dv_js), dq_i

        dk0 = jnp.zeros((nk, B, kb, K, dh), jnp.float32)
        dv0 = jnp.zeros((nk, B, kb, K, dvd), jnp.float32)
        q_xs = ((qs, dos, lses, Ds, qps, qss) if seg
                else (qs, dos, lses, Ds, qps))
        (dk_stk, dv_stk), dq_blocks = jax.lax.scan(
            q_step, (dk0, dv0), q_xs)
        dq = dq_blocks.transpose(1, 2, 0, 3, 4, 5, 6).reshape(
            B, T, Slp, K, G, dh)[:, :, :Sloc].reshape(B, Sq, K, G, dh)
        dk = dk_stk.transpose(1, 0, 2, 3, 4).reshape(
            B, nk * kb, K, dh)[:, :Skv]
        dvv = dv_stk.transpose(1, 0, 2, 3, 4).reshape(
            B, nk * kb, K, dvd)[:, :Skv]
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dvv.astype(v.dtype))

    fa.defvjp(fwd, bwd)
    return fa(q, k, v)


def _swa_gather_attention(q, k, v, q_pos, kv_pos, spec, scale, q_block: int):
    """Sliding-window path: each query block gathers only its KV window —
    O(S·(W+qb)) work instead of O(S²) (danube SWA prefill at 32k+)."""
    B, Sq, K, G, dh = q.shape
    W = spec.window
    qb = min(q_block, Sq)
    pq = (-Sq) % qb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=-1)
    nq = q.shape[1] // qb
    span = W + qb  # static window slice length per query block
    # pad kv on the left by span so dynamic_slice never clamps awkwardly
    k_pad = jnp.pad(k, ((0, 0), (span, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (span, 0), (0, 0), (0, 0)))
    kvp_pad = jnp.pad(kv_pos, (span, 0), constant_values=-(2**30))

    qs = q.reshape(B, nq, qb, K, G, dh).transpose(1, 0, 2, 3, 4, 5)
    qps = q_pos.reshape(nq, qb)
    starts = jnp.arange(nq) * qb  # query block start index into kv

    def q_step(_, q_in):
        qi, qp, s = q_in
        # kv window covering original [s - W, s + qb): padded index p maps
        # to original p - span, so slice at p0 = s + qb, length span.
        p0 = s + qb
        ki = jax.lax.dynamic_slice_in_dim(k_pad, p0, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v_pad, p0, span, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(kvp_pad, p0, span, axis=0)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                            preferred_element_type=jnp.float32) * scale
        mask = _mask_block(qp, kp, spec, None)[None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(vi.dtype), vi)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qs, qps, starts))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qb, K, G, dh)
    return out[:, :Sq]


def _flash_kernel(K: int):
    """The flash kernels' entry for the mesh in context, or None where they
    cannot run.  A ``pallas_call`` has no partitioner: with no multi-device
    mesh it runs whole; on a mesh whose tensor-parallel axis (as
    ``rules.MeshAxes`` resolves it) divides the K kv heads it runs inside
    ``shard_map``, each device on its own heads; on any other mesh the
    ``jnp`` scan partitions instead."""
    from repro.kernels.flash_attention import ops
    from repro.sharding.rules import MeshAxes
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return ops.flash_attention
    axes = MeshAxes(mesh)
    tp = axes.size(axes.tp)
    if tp > 1 and K % tp == 0:
        return functools.partial(ops.flash_attention_sharded, mesh=mesh,
                                 head_axis=axes.tp[0])
    return None


def attention(
    q: Array,              # [B, Sq, H, dh]
    k: Array,              # [B, Skv, K, dh]
    v: Array,              # [B, Skv, K, dh]
    *,
    spec: MaskSpec,
    q_pos: Array,          # (Sq,) int32 positions, or (B, Sq) when packed
    kv_pos: Array,         # (Skv,) int32, or (B, Skv)
    prefix_len: Optional[Array] = None,   # (B,) for prefix-LM
    q_seg: Optional[Array] = None,        # (B, Sq) segment ids (packed)
    kv_seg: Optional[Array] = None,       # (B, Skv)
    scale: Optional[float] = None,
    force_direct: bool = False,
    use_flash_vjp: bool = True,   # False inside lax.cond (jax lowering bug)
) -> Array:
    """GQA attention dispatcher. Returns [B, Sq, H, dv] (dv = v head dim)."""
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    assert H % K == 0, (H, K)
    assert k.shape[-1] == dh, (k.shape, dh)
    assert spec.segmented == (q_seg is not None), \
        "MaskSpec.segmented must match whether segment ids are passed"
    if q_seg is not None:
        assert not spec.has_prefix, \
            "packed-segment batches are incompatible with prefix-LM masks"
    dv = v.shape[-1]
    G = H // K
    qg = q.reshape(B, Sq, K, G, dh)
    scale = scale if scale is not None else dh ** -0.5
    Skv = k.shape[1]

    with jax.named_scope("attention"):
        if force_direct or max(Sq, Skv) <= _DIRECT_ATTN_MAX_SEQ:
            mask = _mask_block(q_pos, kv_pos, spec, prefix_len,
                               q_seg=q_seg, kv_seg=kv_seg)
            mask = (mask[None, None, None] if mask.ndim == 2
                    else mask[:, None, None])
            out = _direct_attention(qg, k, v, mask, scale)
        elif (spec.window is not None and not spec.has_prefix
              and q_seg is None and Skv > spec.window + _Q_BLOCK):
            out = _swa_gather_attention(qg, k, v, q_pos, kv_pos, spec,
                                        scale, _Q_BLOCK)
        else:
            from repro.sharding.act import seq_tiles
            tiles = seq_tiles(Sq)
            k = shard_act(k, "kv_full")
            v = shard_act(v, "kv_full")
            kernel = (_flash_kernel(K) if use_flash_vjp and tiles == 1
                      and Sq == Skv and not spec.has_prefix
                      and jax.default_backend() == "tpu" else None)
            if kernel is not None:
                out = kernel(qg, k, v, q_pos, kv_pos, scale=scale,
                             causal=spec.causal, window=spec.window,
                             q_seg=q_seg, kv_seg=kv_seg)
            else:
                impl = _flash_attention if use_flash_vjp else _block_attention
                out = impl(qg, k, v, q_pos, kv_pos, spec, prefix_len,
                           scale, _Q_BLOCK, _KV_BLOCK, tiles=tiles,
                           q_seg=q_seg, kv_seg=kv_seg)
    return out.reshape(B, Sq, H, dv)


def decode_attention(
    q: Array,              # [B, 1, H, dh]
    k_cache: Array,        # [B, W, K, dh]  (ring buffer or linear cache)
    v_cache: Array,
    *,
    kv_pos: Array,         # [B, W] int32 absolute positions, -1 = empty
    q_pos: Array,          # [B] int32
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> Array:
    """Single-token decode attention over a KV cache. O(W) per token."""
    B, _, H, dh = q.shape
    K = k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(B, 1, K, G, dh)
    with jax.named_scope("attention"):
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
        valid = (kv_pos >= 0) & (kv_pos[:, :] <= q_pos[:, None])
        if window is not None:
            valid = valid & (q_pos[:, None] - kv_pos < window)
        logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v_cache.dtype),
                         v_cache)
    return out.reshape(B, 1, H, dh)


# --------------------------------------------------------------------------
# Dense / linear helpers
# --------------------------------------------------------------------------

def dense(x: Array, w: Array, b: Optional[Array] = None) -> Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def glu_mlp(params: dict, x: Array, act: str = "silu") -> Array:
    """SwiGLU/GeGLU: down( act(gate(x)) * up(x) )."""
    with jax.named_scope("mlp"):
        g = shard_act(dense(x, params["w_gate"]), "ffn")
        u = shard_act(dense(x, params["w_up"]), "ffn")
        return shard_act(dense(ACTS[act](g) * u, params["w_down"]),
                         "hidden")


def mlp(params: dict, x: Array, act: str = "gelu") -> Array:
    """Plain 2-layer MLP (whisper)."""
    with jax.named_scope("mlp"):
        h = ACTS[act](shard_act(dense(x, params["w_up"], params.get("b_up")),
                                "ffn"))
        return shard_act(dense(h, params["w_down"], params.get("b_down")),
                         "hidden")


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, *, scale: float = 1.0,
                dtype=jnp.float32) -> Array:
    std = scale * (d_in ** -0.5)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, *, dtype=jnp.float32) -> Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)
