"""Pallas TPU kernel: fused AdaLomo optimizer step for one m×n tensor.

Why a kernel: inside the fused backward, the AdaLomo update is the sole
consumer of each layer's gradient.  A naive XLA lowering materializes g²,
the rank-1 reconstruction v = outer(r,c)/sum(r), u, and û as HBM-sized
temporaries; this kernel keeps every [m,n] intermediate in VMEM tiles, so
the only HBM traffic is grad/param reads and the param write, and the only
extra state ever allocated is the O(m+n) factored moments — the Table-1
memory claim enforced at kernel level.

Two ``pallas_call``s (cross-tensor reductions force phase boundaries):

  A (stats):  one sweep of g emits rowsum(g²+ε) as an [m,1] column and
              colsum(g²+ε) as one [1,n] partial per row block.
  host:       r' = βr + (1-β)·rowsum, c' = βc + (1-β)·Σ partials,
              denom = Σr', bias correction (O(m+n) work, jnp).
  B (update): phase 0 sweeps g to accumulate Σu² and Σp² in VMEM scratch
              (u recomputed from (r',c'), never stored); phase 1 applies
              û = u/max(1,RMS(u)/d)·max(ε₂,RMS(θ)) and writes θ' in-place.

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
    overwritten that block with θ'.

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

def _update_kernel(scal_ref, p_ref, g_ref, r_ref, c_ref, p_out, uu_ref,
                   pp_ref):
    phase = pl.program_id(0)
    i, j = pl.program_id(1), pl.program_id(2)
    (inv_denom_corr, eps_div, lr, clip, eps_rms, n_elems,
     literal, decay) = (scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3],
                        scal_ref[4], scal_ref[5], scal_ref[6], scal_ref[7])

    @pl.when((phase == 0) & (i == 0) & (j == 0))
    def _():
        uu_ref[...] = jnp.zeros_like(uu_ref)   # column partials of Σu²
        pp_ref[...] = jnp.zeros_like(pp_ref)   # column partials of Σp²

    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    v_hat = (r_ref[...] * c_ref[...]) * inv_denom_corr   # [bm,1]*[1,bn]
    u = jnp.where(literal > 0.5,
                  g / (v_hat + eps_div),
                  g / (jnp.sqrt(v_hat) + eps_div))

    @pl.when(phase == 0)
    def _():
        uu_ref[...] += jnp.sum(u * u, axis=0, keepdims=True)
        pp_ref[...] += jnp.sum(p * p, axis=0, keepdims=True)

    @pl.when(phase == 1)
    def _():
        rms_u = jnp.sqrt(jnp.sum(uu_ref[...], axis=1, keepdims=True)
                         / n_elems)                       # [1, 1]
        rms_p = jnp.sqrt(jnp.sum(pp_ref[...], axis=1, keepdims=True)
                         / n_elems)
        scale = jnp.maximum(eps_rms, rms_p) / jnp.maximum(1.0, rms_u / clip)
        # Decoupled weight decay applied at write time: Σp² (hence the
        # RMS(θ) trust scale) is accumulated from the *un-decayed* θ in
        # phase 0, matching core.adalomo.update_tensor exactly.
        p_out[...] = (p * decay - lr * u * scale).astype(p_out.dtype)


def update_pallas(param, grad, r_new, c_new, *, lr, inv_denom_corr,
                  eps_div, clip, eps_rms, n_elems, decay=1.0, literal=False,
                  block=DEFAULT_BLOCK, interpret=False):
    """θ' for param/grad [m,n] given the new moments r_new [m,1], c_new
    [1,n]; the output reuses θ's buffer."""
    m, n = param.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (
        f"param shape {(m, n)} not a multiple of block {(bm, bn)}")
    grid = (2, m // bm, n // bn)
    scal = jnp.array([inv_denom_corr, eps_div, lr, clip, eps_rms,
                      float(n_elems), 1.0 if literal else 0.0, decay],
                     jnp.float32)
    return pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda p, i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda p, i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda p, i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda p, i, j: (0, j)),
        ],
        # phase 0 parks the output on block (0,0), which phase 1 writes
        # first: no stale VMEM ever reaches the aliased θ
        out_specs=pl.BlockSpec((bm, bn), lambda p, i, j: (i * p, j * p)),
        out_shape=jax.ShapeDtypeStruct((m, n), param.dtype),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.float32)],
        input_output_aliases={1: 0},   # param buffer reused for output
        interpret=interpret,
    )(scal, param, grad, r_new, c_new)
