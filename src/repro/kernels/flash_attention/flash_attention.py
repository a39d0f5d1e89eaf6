"""Pallas TPU kernels: flash attention forward and backward for training.

The training step's long-sequence attention (``models/layers.py``,
``max(Sq, Skv) > 2048``) runs as three ``pallas_call``s, each streaming
K/V and Q tiles through VMEM so no score or probability block ever
reaches HBM:

  flash_attention_fwd      online softmax over kv blocks; writes the
                           output and the per-row log-sum-exp.
  flash_attention_bwd_dkv  dK, dV for one kv block, accumulated over the
                           q blocks (FlashAttention-2's outer kv loop).
  flash_attention_bwd_dq   dQ for one q block, accumulated over the kv
                           blocks.

The backward is a dq/dkv pair rather than one kernel: each output block
is finished in one run of consecutive grid steps, so dQ (summed over kv
blocks) and dK/dV (summed over q blocks) need opposite loop orders; the
pair recomputes P twice instead of keeping a whole head's dQ in VMEM.

Tile layout.  The G = H/K query heads of one kv head share a q tile of
R = G·bq rows, so each K/V tile is fetched once per group.  Every kernel
works on transposed scores ``sᵀ = k·qᵀ`` of shape (bk, R): kv positions
on sublanes, the q tile on lanes.  The softmax statistics (m, l, lse, D)
are then lane-dense rows reduced over sublanes, and the q-side tiles are
stored row-major ``[B, K, nq, R, d]`` while outputs that the MXU produces
transposed (``oᵀ``, ``dqᵀ``) are stored as ``[B, K, nq, d, R]``; ``ops.py``
moves between these and the model's ``[B, S, K, G, d]``.

Block skipping.  A scalar-prefetched table classes each (q block, kv
block) pair as skipped (0), partial (1: the element mask is applied) or
full (2: every element visible), from the blocks' position and segment
ranges.  Skipped pairs do no compute, and the K/V (or Q) ``index_map``
clamps the block index to the visible range of the row, so a skipped
step names the block already in VMEM and issues no copy.

Precision: the MXU takes q, k, v, dO and the probabilities in the model
dtype with float32 accumulation; softmax statistics and accumulators are
float32; exp and division are exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Query positions per tile (the tile has G times as many rows) and kv
# positions per block, chosen on a v5e (PERF.md, "block sizes").
BLOCK_Q = 512
BLOCK_KV = 512

NEG_INF = -1e30
# Padded metadata slots: a padded query (pos -1, seg -1) and a padded kv
# (pos 2**30, seg -2) never pass causal, window or segment terms.
QPOS_FILL, KPOS_FILL = -1, 2 ** 30
QSEG_FILL, KSEG_FILL = -1, -2

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_VMEM_LIMIT = 64 * 2 ** 20
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


# --------------------------------------------------------------------------
# Block plan: metadata tiles and the (q block, kv block) class table
# --------------------------------------------------------------------------

def _blocks(x, n, b):
    return x.reshape(x.shape[0], n, b)


def plan(q_pos, kv_pos, q_seg, kv_seg, *, G, bq, bk, causal, window):
    """Padded position/segment tiles and block tables for one call.

    q_pos/q_seg: (B, Sq) int; kv_pos/kv_seg: (B, Skv) int (segments may be
    None).  Returns a dict of arrays: ``cls`` [B·nq·nk] (0 skip, 1
    partial, 2 full), ``kv_lo``/``kv_hi`` [B·nq] (the first and last kv
    block each q block sees), ``q_lo``/``q_hi`` [B·nk], the query metadata
    as tile rows [B, nq, 1, R] (``qp``, ``qs``) and the kv metadata as
    columns [B, Skvp, 1] (``kp``, ``ks``)."""
    B, Sq = q_pos.shape
    Skv = kv_pos.shape[1]
    pq, pk = (-Sq) % bq, (-Skv) % bk
    nq, nk = (Sq + pq) // bq, (Skv + pk) // bk

    def pad(x, p, fill):
        return jnp.pad(x.astype(jnp.int32), ((0, 0), (0, p)),
                       constant_values=fill)

    qp, kp = pad(q_pos, pq, QPOS_FILL), pad(kv_pos, pk, KPOS_FILL)
    qb, kb = _blocks(qp, nq, bq), _blocks(kp, nk, bk)
    qmin, qmax = qb.min(-1)[:, :, None], qb.max(-1)[:, :, None]
    kmin, kmax = kb.min(-1)[:, None, :], kb.max(-1)[:, None, :]
    vis = jnp.ones((B, nq, nk), bool)
    full = jnp.ones((B, nq, nk), bool)
    if causal:
        vis &= qmax >= kmin
        full &= qmin >= kmax
    if window is not None:
        vis &= qmin - kmax < window
        full &= qmax - kmin < window
    out = {}
    if q_seg is not None:
        qs, ks = pad(q_seg, pq, QSEG_FILL), pad(kv_seg, pk, KSEG_FILL)
        sb, tb = _blocks(qs, nq, bq), _blocks(ks, nk, bk)
        smin, smax = sb.min(-1)[:, :, None], sb.max(-1)[:, :, None]
        tmin, tmax = tb.min(-1)[:, None, :], tb.max(-1)[:, None, :]
        vis &= (smax >= tmin) & (tmax >= smin)
        full &= (smin == smax) & (tmin == tmax) & (smin == tmin)
        out["qs"] = _tile_rows(sb, G)
        out["ks"] = ks[:, :, None]
    if pk:  # the last kv block holds padding: mask it element by element
        full &= (jnp.arange(nk) < nk - 1)[None, None, :]
    cls = jnp.where(vis, jnp.where(full, 2, 1), 0).astype(jnp.int32)

    def first_last(v, axis):
        n = v.shape[axis]
        idx = jnp.arange(n, dtype=jnp.int32)
        idx = idx[:, None] if axis == 1 else idx[None, :]
        lo = jnp.min(jnp.where(v, idx, n), axis=axis)
        hi = jnp.max(jnp.where(v, idx, -1), axis=axis)
        none = hi < 0
        return (jnp.where(none, 0, lo).reshape(-1).astype(jnp.int32),
                jnp.where(none, 0, hi).reshape(-1).astype(jnp.int32))

    out["kv_lo"], out["kv_hi"] = first_last(vis, 2)     # per (b, q block)
    out["q_lo"], out["q_hi"] = first_last(vis, 1)       # per (b, kv block)
    out["cls"] = cls.reshape(-1)
    out["qp"] = _tile_rows(qb, G)
    out["kp"] = kp[:, :, None]
    return out


def _tile_rows(x, G):
    """[B, nq, bq] -> [B, nq, 1, G·bq]: one q tile's metadata as a row,
    repeated for each of its G heads."""
    B, nq, bq = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (B, nq, G, bq)).reshape(
        B, nq, 1, G * bq)


def _mask(qp, kp, qs, ks, *, causal, window, kv_len, kv_block, bk):
    """Element mask (bk, R) of one pair: the rule of ``layers._mask_block``
    (q ≥ k; q − k < window; equal segments), plus kv padding."""
    m = None

    def both(a, b):
        return b if a is None else a & b

    if causal:
        m = both(m, qp >= kp)
    if window is not None:
        m = both(m, qp - kp < window)
    if qs is not None:
        m = both(m, qs == ks)
    if kv_len is not None:
        row = kv_block * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        m = both(m, row < kv_len)
    return m


def _seg_refs(rest, seg):
    """(q segment ref, kv segment ref, the other refs): with segment ids
    their two refs lead ``rest``; without, they are None."""
    return (rest[0], rest[1], rest[2:]) if seg else (None, None, rest)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(cls_ref, lo_ref, hi_ref, q_ref, k_ref, vt_ref, qp_ref,
                kp_ref, *rest, scale, nq, nk, seg, mask_kw):
    qs_ref, ks_ref, refs = _seg_refs(rest, seg)
    ot_ref, lse_ref, m_sc, l_sc, acc_sc = refs
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    row = b * nq + i
    kv_block = jnp.clip(j, lo_ref[row], hi_ref[row])
    c = cls_ref[row * nk + j]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        k = k_ref[...]
        s = jax.lax.dot_general(k, q_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(qp_ref[...], kp_ref[...],
                                qs_ref[...] if seg else None,
                                ks_ref[...] if seg else None,
                                kv_block=kv_block, bk=k.shape[0], **mask_kw),
                          s, NEG_INF)
        m_prev = m_sc[...]                                   # (1, R)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                               # (bk, R)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        vt = vt_ref[...]                                     # (dv, bk)
        acc_sc[...] = acc_sc[...] * corr + jnp.dot(
            vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    pl.when(c == 2)(lambda: step(False))
    pl.when(c == 1)(lambda: step(True))

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_sc[...], 1e-30)
        ot_ref[...] = (acc_sc[...] / l).astype(ot_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


class _Grid:
    """Block specs for a grid (b, h, x, y) whose step reads q block
    ``q_of(b, x, y, *tables)`` and kv block ``kv_of(b, x, y, *tables)``
    (``tables``: the scalar-prefetched class table and the clamp range)."""

    def __init__(self, R, bk, q_of, kv_of):
        self.R, self.bk = R, bk
        self._q = lambda b, h, x, y, *t: q_of(b, x, y, *t)
        self._kv = lambda b, h, x, y, *t: kv_of(b, x, y, *t)

    def q_tile(self, d):          # [B, K, nq, R, d]
        return pl.BlockSpec((None, None, None, self.R, d),
                            lambda *a: (a[0], a[1], self._q(*a), 0, 0))

    def q_tile_t(self, d):        # [B, K, nq, d, R]
        return pl.BlockSpec((None, None, None, d, self.R),
                            lambda *a: (a[0], a[1], self._q(*a), 0, 0))

    def q_row(self):              # [B, K, nq, 1, R] statistics
        return self.q_tile_t(1)

    def q_meta(self):             # [B, nq, 1, R] positions / segments
        return pl.BlockSpec((None, None, 1, self.R),
                            lambda *a: (a[0], self._q(*a), 0, 0))

    def kv_tile(self, d):         # [B, K, Skvp, d]
        return pl.BlockSpec((None, None, self.bk, d),
                            lambda *a: (a[0], a[1], self._kv(*a), 0))

    def kv_tile_t(self, d):       # [B, K, d, Skvp]
        return pl.BlockSpec((None, None, d, self.bk),
                            lambda *a: (a[0], a[1], 0, self._kv(*a)))

    def kv_meta(self):            # [B, Skvp, 1] positions / segments
        return pl.BlockSpec((None, self.bk, 1),
                            lambda *a: (a[0], self._kv(*a), 0))


def _q_major(R, bk, nq):
    """Grid (b, h, q block i, kv block j): kv clamped to what i sees."""
    return _Grid(R, bk, lambda b, i, j, *t: i,
                 lambda b, i, j, cls, lo, hi: jnp.clip(
                     j, lo[b * nq + i], hi[b * nq + i]))


def _kv_major(R, bk, nk):
    """Grid (b, h, kv block j, q block i): q clamped to what sees j."""
    return _Grid(R, bk,
                 lambda b, j, i, cls, lo, hi: jnp.clip(
                     i, lo[b * nk + j], hi[b * nk + j]),
                 lambda b, j, i, *t: j)


def _call(kernel, name, grid, tables, in_specs, args, meta, g, out_specs,
          out_shape, scratch, interpret):
    """One pallas_call; with segment ids, their q-row and kv-column blocks
    follow the positions'."""
    in_specs = in_specs + [g.q_meta(), g.kv_meta()]
    args = args + [meta["qp"], meta["kp"]]
    if "qs" in meta:
        in_specs += [g.q_meta(), g.kv_meta()]
        args += [meta["qs"], meta["ks"]]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, compiler_params=_PARAMS, interpret=interpret,
        name=name,
    )(*tables, *args)


@functools.partial(jax.jit, static_argnames=(
    "bk", "scale", "causal", "window", "kv_len", "interpret"))
def flash_fwd(qt, kt, vtt, meta, *, bk, scale, causal, window, kv_len,
              interpret=False):
    """qt [B,K,nq,R,dh]; kt [B,K,Skvp,dh]; vtt [B,K,dv,Skvp].
    Returns oᵀ [B,K,nq,dv,R] and lse [B,K,nq,1,R] (float32)."""
    B, K, nq, R, dh = qt.shape
    Skvp, dv = kt.shape[2], vtt.shape[2]
    nk = Skvp // bk
    g = _q_major(R, bk, nq)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, nq=nq, nk=nk, seg="qs" in meta,
        mask_kw=dict(causal=causal, window=window, kv_len=kv_len))
    return _call(
        kernel, "flash_attention_fwd", (B, K, nq, nk),
        (meta["cls"], meta["kv_lo"], meta["kv_hi"]),
        [g.q_tile(dh), g.kv_tile(dh), g.kv_tile_t(dv)], [qt, kt, vtt],
        meta, g, [g.q_tile_t(dv), g.q_row()],
        [jax.ShapeDtypeStruct((B, K, nq, dv, R), qt.dtype),
         jax.ShapeDtypeStruct((B, K, nq, 1, R), jnp.float32)],
        [pltpu.VMEM((1, R), jnp.float32), pltpu.VMEM((1, R), jnp.float32),
         pltpu.VMEM((dv, R), jnp.float32)], interpret)


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _grad_terms(k, v, q, do, lse, dd, qp, kp, qs, ks, *, masked, scale,
                kv_block, mask_kw):
    """Pᵀ and dSᵀ (bk, R) of one pair, recomputed from q, k and lse."""
    s = jax.lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)
    if masked:
        mask = _mask(qp, kp, qs, ks, kv_block=kv_block, bk=k.shape[0],
                     **mask_kw)
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - dd)


def _dkv_kernel(cls_ref, lo_ref, hi_ref, q_ref, do_ref, k_ref, v_ref,
                lse_ref, dd_ref, qp_ref, kp_ref, *rest, scale, nq, nk, seg,
                mask_kw):
    qs_ref, ks_ref, (dk_ref, dv_ref, dk_sc, dv_sc) = _seg_refs(rest, seg)
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    c = cls_ref[(b * nq + i) * nk + j]

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        p, ds = _grad_terms(
            k_ref[...], v_ref[...], q, do, lse_ref[...], dd_ref[...],
            qp_ref[...], kp_ref[...], qs_ref[...] if seg else None,
            ks_ref[...] if seg else None, masked=masked, scale=scale,
            kv_block=j, mask_kw=mask_kw)
        dv_sc[...] += jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dk_sc[...] += jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)

    pl.when(c == 2)(lambda: step(False))
    pl.when(c == 1)(lambda: step(True))

    @pl.when(i == nq - 1)
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(cls_ref, lo_ref, hi_ref, q_ref, do_ref, k_ref, v_ref, kt_ref,
               lse_ref, dd_ref, qp_ref, kp_ref, *rest, scale, nq, nk, seg,
               mask_kw):
    qs_ref, ks_ref, (dqt_ref, acc_sc) = _seg_refs(rest, seg)
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    row = b * nq + i
    kv_block = jnp.clip(j, lo_ref[row], hi_ref[row])
    c = cls_ref[row * nk + j]

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        _, ds = _grad_terms(
            k_ref[...], v_ref[...], q_ref[...], do_ref[...], lse_ref[...],
            dd_ref[...], qp_ref[...], kp_ref[...],
            qs_ref[...] if seg else None, ks_ref[...] if seg else None,
            masked=masked, scale=scale, kv_block=kv_block, mask_kw=mask_kw)
        kt = kt_ref[...]                                      # (dh, bk)
        acc_sc[...] += jnp.dot(kt, ds.astype(kt.dtype),
                               preferred_element_type=jnp.float32)

    pl.when(c == 2)(lambda: step(False))
    pl.when(c == 1)(lambda: step(True))

    @pl.when(j == nk - 1)
    def _():
        dqt_ref[...] = (acc_sc[...] * scale).astype(dqt_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bk", "scale", "causal", "window", "kv_len", "interpret"))
def flash_bwd(qt, dot, kt, vt, lse, dd, meta, *, bk, scale, causal, window,
              kv_len, interpret=False):
    """qt [B,K,nq,R,dh]; dot [B,K,nq,R,dv] (dO); kt/vt [B,K,Skvp,dh|dv];
    lse, dd (D = rowsum(dO∘O)) [B,K,nq,1,R] float32.
    Returns dqᵀ [B,K,nq,dh,R], dk [B,K,Skvp,dh], dv [B,K,Skvp,dv]."""
    B, K, nq, R, dh = qt.shape
    Skvp, dv = kt.shape[2], vt.shape[3]
    nk = Skvp // bk
    kw = dict(scale=scale, nq=nq, nk=nk, seg="qs" in meta,
              mask_kw=dict(causal=causal, window=window, kv_len=kv_len))

    g = _kv_major(R, bk, nk)
    dk, dvv = _call(
        functools.partial(_dkv_kernel, **kw), "flash_attention_bwd_dkv",
        (B, K, nk, nq), (meta["cls"], meta["q_lo"], meta["q_hi"]),
        [g.q_tile(dh), g.q_tile(dv), g.kv_tile(dh), g.kv_tile(dv),
         g.q_row(), g.q_row()], [qt, dot, kt, vt, lse, dd],
        meta, g, [g.kv_tile(dh), g.kv_tile(dv)],
        [jax.ShapeDtypeStruct(kt.shape, kt.dtype),
         jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        [pltpu.VMEM((bk, dh), jnp.float32), pltpu.VMEM((bk, dv), jnp.float32)],
        interpret)

    g = _q_major(R, bk, nq)
    dqt = _call(
        functools.partial(_dq_kernel, **kw), "flash_attention_bwd_dq",
        (B, K, nq, nk), (meta["cls"], meta["kv_lo"], meta["kv_hi"]),
        [g.q_tile(dh), g.q_tile(dv), g.kv_tile(dh), g.kv_tile(dv),
         g.kv_tile_t(dh), g.q_row(), g.q_row()],
        [qt, dot, kt, vt, jnp.swapaxes(kt, 2, 3), lse, dd],
        meta, g, g.q_tile_t(dh),
        jax.ShapeDtypeStruct((B, K, nq, dh, R), qt.dtype),
        [pltpu.VMEM((dh, R), jnp.float32)], interpret)
    return dqt, dk, dvv
