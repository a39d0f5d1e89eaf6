"""Pallas TPU kernel: fused AdaLomo optimizer step for one m×n tensor.

Why a kernel: inside the fused backward, the AdaLomo update is the sole
consumer of each layer's gradient.  A naive XLA lowering materializes g²,
the rank-1 reconstruction v = outer(r,c)/sum(r), u, and û as HBM-sized
temporaries; this kernel keeps every [m,n] intermediate in VMEM tiles, so
the only HBM traffic is grad/param reads and the param write, and the only
extra state ever allocated is the O(m+n) factored moments — the Table-1
memory claim enforced at kernel level.

Two ``pallas_call``s for a whole tensor (cross-tensor reductions force
phase boundaries):

  A (stats):  one sweep of g emits rowsum(g²+ε) as an [m,1] column and
              colsum(g²+ε) as one [1,n] partial per row block.
  host:       r' = βr + (1-β)·rowsum, c' = βc + (1-β)·Σ partials,
              denom = Σr', bias correction (O(m+n) work, jnp).
  B (update): phase 0 sweeps g to accumulate Σu² and Σp² in VMEM scratch
              (u recomputed from (r',c'), never stored); phase 1 applies
              û = u/max(1,RMS(u)/d)·max(ε₂,RMS(θ)) and writes θ' in-place.

A shard of a tensor (``ops.adalomo_update`` inside ``shard_map``) cannot
finish Σu² and Σθ² inside one call: the other shards hold the rest.  So
there B runs as two calls, B1 (sums: the column partials of Σu² and Σθ²)
and B2 (apply: θ' from the sums, in place), and the host step between A
and B1, and between B1 and B2, adds the shards' row and column sums, Σr'
and B1's partials across the mesh: each chip updates its own shard with
the statistics of the whole tensor.

TPU constraints that shape the layout:

  * the factored moments travel as 2-D ``[m,1]`` / ``[1,n]`` arrays, so
    their blocks tile like any other (8,128)-aligned operand (1-D blocks
    disagree with the layout XLA gives a 1-D f32 vector);
  * an output block is written back to HBM whenever its block index
    changes and is never read back, so every output block is finished in
    one run of consecutive grid steps: rowsums accumulate over the inner
    (column) axis, column sums are emitted per row block and reduced on
    the host side;
  * kernel B's output aliases θ, so its index map stays on block (0,0)
    through phase 0 — nothing is written back before phase 1 has
    overwritten that block with θ'; B1's two outputs keep one block index
    through the whole grid, so they stay in VMEM as accumulators and are
    written back once.

Block shapes default to (256, 512) tiles — (8,128)-lane aligned, ~0.5 MB
each in fp32, comfortably inside the ~16 MB VMEM envelope with all four
operands double-buffered.  Edge tiles are handled by zero-padding in ops.py
(zero rows/cols contribute only ε to the statistics and nothing to the
RMS sums; true element counts travel in the scalar operand).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (256, 512)


# --------------------------------------------------------------------------
# Kernel A: factored second-moment statistics
# --------------------------------------------------------------------------

def _stats_kernel(scal_ref, g_ref, row_out, col_out):
    j = pl.program_id(1)
    g = g_ref[...].astype(jnp.float32)
    g2 = g * g + scal_ref[0]
    row_part = jnp.sum(g2, axis=1, keepdims=True)     # [bm, 1]

    @pl.when(j == 0)
    def _():
        row_out[...] = row_part

    @pl.when(j != 0)
    def _():
        row_out[...] += row_part

    col_out[...] = jnp.sum(g2, axis=0, keepdims=True)  # [1, bn]


def stats_pallas(grad, *, eps_stat, block=DEFAULT_BLOCK, interpret=False):
    """(rowsum [m,1], per-row-block column sums [m/bm, 1, n]) of g²+ε."""
    m, n = grad.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (
        f"grad shape {(m, n)} not a multiple of block {(bm, bn)}")
    grid = (m // bm, n // bn)
    scal = jnp.array([eps_stat], jnp.float32)
    return pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m // bm, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(scal, grad)


# --------------------------------------------------------------------------
# Kernel B: grouped-normalized update, applied in place
# --------------------------------------------------------------------------
#
# One body of arithmetic for both forms: ``_u`` rebuilds u of a block from
# (r', c'), ``_sums`` gives its column partials of Σu² and Σθ², ``_apply``
# writes θ' from the finished sums.  The scalar operand has one layout
# (``_scalars``) for every call.

def _scalars(inv_denom_corr, eps_div, literal, lr=0.0, clip=0.0, eps_rms=0.0,
             n_elems=0.0, decay=0.0):
    """The scalar operand, [1, 8]: a whole row, so that under ``vmap``
    (stacked or expert tensors, whose Σr' differs per tensor) the batched
    operand is blocked as a whole row per grid step."""
    return jnp.array([[inv_denom_corr, eps_div, lr, clip, eps_rms, n_elems,
                       1.0 if literal else 0.0, decay]], jnp.float32)


def _u(scal_ref, g_ref, r_ref, c_ref):
    """u = g / (sqrt(v̂) + ε) of one block, v̂ rebuilt from (r', c')."""
    inv_denom_corr, eps_div, literal = (scal_ref[0, 0], scal_ref[0, 1],
                                        scal_ref[0, 6])
    g = g_ref[...].astype(jnp.float32)
    v_hat = (r_ref[...] * c_ref[...]) * inv_denom_corr   # [bm,1]*[1,bn]
    return jnp.where(literal > 0.5,
                     g / (v_hat + eps_div),
                     g / (jnp.sqrt(v_hat) + eps_div))


def _sums(u, p):
    """Column partials ([1, bn]) of Σu² and Σθ² of one block."""
    return (jnp.sum(u * u, axis=0, keepdims=True),
            jnp.sum(p * p, axis=0, keepdims=True))


def _apply(scal_ref, p, u, uu, pp):
    """θ' of one block, û scaled by the RMS of u and of θ from the lane
    partials ``uu``, ``pp`` of the whole tensor."""
    lr, clip, eps_rms, n_elems, decay = (scal_ref[0, 2], scal_ref[0, 3],
                                         scal_ref[0, 4], scal_ref[0, 5],
                                         scal_ref[0, 7])
    rms_u = jnp.sqrt(jnp.sum(uu, axis=1, keepdims=True) / n_elems)  # [1, 1]
    rms_p = jnp.sqrt(jnp.sum(pp, axis=1, keepdims=True) / n_elems)
    scale = jnp.maximum(eps_rms, rms_p) / jnp.maximum(1.0, rms_u / clip)
    # Decoupled weight decay applied at write time: Σp² (hence the
    # RMS(θ) trust scale) is summed from the *un-decayed* θ, matching
    # core.adalomo.update_tensor exactly.
    return p * decay - lr * u * scale


def _operand_specs(bm, bn, index):
    """Specs of (scalars, θ, g, r', c'); ``index`` maps grid indices to
    the block (i, j) of θ and g."""
    return [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((bm, bn), index),
        pl.BlockSpec((bm, bn), index),
        pl.BlockSpec((bm, 1), lambda *g: (index(*g)[0], 0)),
        pl.BlockSpec((1, bn), lambda *g: (0, index(*g)[1])),
    ]


def _blocks(param, block):
    """(m, n, bm, bn, grid of (m/bm, n/bn) blocks) for param [m, n]."""
    m, n = param.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (
        f"param shape {(m, n)} not a multiple of block {(bm, bn)}")
    return m, n, bm, bn, (m // bm, n // bn)


def _update_kernel(scal_ref, p_ref, g_ref, r_ref, c_ref, p_out, uu_ref,
                   pp_ref):
    phase = pl.program_id(0)
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((phase == 0) & (i == 0) & (j == 0))
    def _():
        uu_ref[...] = jnp.zeros_like(uu_ref)   # column partials of Σu²
        pp_ref[...] = jnp.zeros_like(pp_ref)   # column partials of Σp²

    u = _u(scal_ref, g_ref, r_ref, c_ref)
    p = p_ref[...].astype(jnp.float32)

    @pl.when(phase == 0)
    def _():
        uu, pp = _sums(u, p)
        uu_ref[...] += uu
        pp_ref[...] += pp

    @pl.when(phase == 1)
    def _():
        p_out[...] = _apply(scal_ref, p, u, uu_ref[...],
                            pp_ref[...]).astype(p_out.dtype)


def update_pallas(param, grad, r_new, c_new, *, lr, inv_denom_corr,
                  eps_div, clip, eps_rms, n_elems, decay=1.0, literal=False,
                  block=DEFAULT_BLOCK, interpret=False):
    """θ' for a whole tensor: param/grad [m,n] given the new moments
    r_new [m,1], c_new [1,n], in one call of two phases (phase 0 sums Σu²
    and Σθ² in VMEM scratch, phase 1 applies); the output reuses θ's
    buffer."""
    m, n, bm, bn, grid = _blocks(param, block)
    scal = _scalars(inv_denom_corr, eps_div, literal, lr, clip, eps_rms,
                    float(n_elems), decay)
    return pl.pallas_call(
        _update_kernel,
        grid=(2,) + grid,
        in_specs=_operand_specs(bm, bn, lambda p, i, j: (i, j)),
        # phase 0 parks the output on block (0,0), which phase 1 writes
        # first: no stale VMEM ever reaches the aliased θ
        out_specs=pl.BlockSpec((bm, bn), lambda p, i, j: (i * p, j * p)),
        out_shape=jax.ShapeDtypeStruct((m, n), param.dtype),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.float32)],
        input_output_aliases={1: 0},   # param buffer reused for output
        interpret=interpret,
    )(scal, param, grad, r_new, c_new)


# --------------------------------------------------------------------------
# Kernel B on a shard: the same arithmetic in two calls (B1, B2)
# --------------------------------------------------------------------------

def _sums_kernel(scal_ref, p_ref, g_ref, r_ref, c_ref, uu_out, pp_out):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        uu_out[...] = jnp.zeros_like(uu_out)   # column partials of Σu²
        pp_out[...] = jnp.zeros_like(pp_out)   # column partials of Σp²

    uu, pp = _sums(_u(scal_ref, g_ref, r_ref, c_ref),
                   p_ref[...].astype(jnp.float32))
    uu_out[...] += uu
    pp_out[...] += pp


def _apply_kernel(scal_ref, p_ref, g_ref, r_ref, c_ref, uu_ref, pp_ref,
                  p_out):
    u = _u(scal_ref, g_ref, r_ref, c_ref)
    p = p_ref[...].astype(jnp.float32)
    p_out[...] = _apply(scal_ref, p, u, uu_ref[...],
                        pp_ref[...]).astype(p_out.dtype)


def sums_pallas(param, grad, r_new, c_new, *, inv_denom_corr, eps_div,
                literal=False, block=DEFAULT_BLOCK, interpret=False):
    """Column partials ([1, bn] each) of Σu² and Σθ² for param/grad [m,n]
    given the new moments r_new [m,1], c_new [1,n].  Summed over the
    lanes they are the grouped normalization's sums; partials of shards
    of one tensor add lane by lane."""
    m, n, bm, bn, grid = _blocks(param, block)
    scal = _scalars(inv_denom_corr, eps_div, literal)
    acc = pl.BlockSpec((1, bn), lambda i, j: (0, 0))
    return pl.pallas_call(
        _sums_kernel,
        grid=grid,
        in_specs=_operand_specs(bm, bn, lambda i, j: (i, j)),
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((1, bn), jnp.float32)] * 2,
        interpret=interpret,
    )(scal, param, grad, r_new, c_new)


def apply_pallas(param, grad, r_new, c_new, uu, pp, *, lr, inv_denom_corr,
                 eps_div, clip, eps_rms, n_elems, decay=1.0, literal=False,
                 block=DEFAULT_BLOCK, interpret=False):
    """θ' = θ·decay - lr·û for param/grad [m,n], û scaled by the RMS of u
    and of θ over ``n_elems`` elements from the lane partials ``uu``,
    ``pp`` of :func:`sums_pallas`; the output reuses θ's buffer."""
    m, n, bm, bn, grid = _blocks(param, block)
    scal = _scalars(inv_denom_corr, eps_div, literal, lr, clip, eps_rms,
                    float(n_elems), decay)
    acc = pl.BlockSpec((1, bn), lambda i, j: (0, 0))
    return pl.pallas_call(
        _apply_kernel,
        grid=grid,
        in_specs=_operand_specs(bm, bn, lambda i, j: (i, j)) + [acc, acc],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), param.dtype),
        input_output_aliases={1: 0},   # param buffer reused for output
        interpret=interpret,
    )(scal, param, grad, r_new, c_new, uu, pp)
