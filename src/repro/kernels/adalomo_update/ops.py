"""Jitted wrapper for the fused AdaLomo update kernel.

``adalomo_update(param, grad, r, c, lr, step, beta, weight_decay, clip)``
— handles padding to block multiples, the O(m+n) moment EMA and r-sum
between the kernels, the sums across a mesh when it updates one shard of
a tensor, leading stack dims via vmap, and exposes ``interpret=`` for CPU
validation against ref.py.

All hyperparameters are dynamic operands (Opt v2 contract): lr/β/decay/
clip may be traced scalars, so schedules and per-group overrides never
recompile the kernel.  The structural knobs (ε's, factoring threshold,
``literal_div_v``) stay in the static :class:`AdaLomoConfig`.

This module exposes the raw 2-D kernel entry point only; optimizer-rule
integration is the ``backend="pallas"`` dispatch inside
``repro.core.optimizers.adalomo`` — there is no separately-registered
kernel rule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.adalomo import DEFAULT_HPARAMS, AdaLomoConfig
from repro.kernels.adalomo_update.adalomo_update import (
    DEFAULT_BLOCK, apply_pallas, stats_pallas, sums_pallas, update_pallas)


def _pad_to(x, bm, bn):
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _psum(x, axes: tuple):
    """Sum over the mesh axes ``axes``, under the named scope
    ``shard_reduce`` (none: x itself)."""
    if not axes:
        return x
    with jax.named_scope("shard_reduce"):
        return jax.lax.psum(x, axes)


@functools.partial(jax.jit, static_argnames=("cfg", "block", "interpret",
                                             "row_axes", "col_axes"))
def adalomo_update(param, grad, r, c, lr, step,
                   beta=DEFAULT_HPARAMS["beta"],
                   weight_decay=DEFAULT_HPARAMS["weight_decay"],
                   clip=DEFAULT_HPARAMS["clip"], *,
                   cfg: AdaLomoConfig = AdaLomoConfig(),
                   block=DEFAULT_BLOCK, interpret: bool = False,
                   row_axes: tuple = (), col_axes: tuple = ()):
    """Fused AdaLomo step for a 2-D tensor (or stacked [..., m, n] via vmap).

    Returns (new_param, new_r, new_c). Semantics == ref.adalomo_update_ref:
    decoupled weight decay scales θ at the final write, while the RMS(θ)
    trust scale is computed from the un-decayed θ.

    Inside ``shard_map``, ``param``/``grad`` may be one shard of the tensor:
    ``row_axes`` name the mesh axes that split its rows, ``col_axes`` its
    columns, and ``r``/``c`` are this shard's rows and columns of the
    moments.  The row sums are then added over ``col_axes``, the column
    sums and Σr' over ``row_axes``, and Σu², Σθ² over both (kernel B in
    its two-call form), under the named scope ``shard_reduce``: the update
    is the whole tensor's.  A whole tensor runs exactly as before any
    mesh: kernel B in one call.
    """
    if param.ndim > 2:
        fn = functools.partial(adalomo_update, cfg=cfg, block=block,
                               interpret=interpret, row_axes=row_axes,
                               col_axes=col_axes)
        return jax.vmap(lambda p, g, rr, cc: fn(
            p, g, rr, cc, lr, step, beta, weight_decay, clip))(
            param, grad, r, c)
    assert param.ndim == 2, param.shape
    m, n = param.shape
    bm, bn = min(block[0], m), min(block[1], n)
    # pad to block multiples (zero rows/cols are inert in every statistic)
    p_p = _pad_to(param, bm, bn)
    g_p = _pad_to(grad, bm, bn)

    rowsum, colsum_parts = stats_pallas(g_p, eps_stat=cfg.eps_stat,
                                        block=(bm, bn), interpret=interpret)
    beta = jnp.asarray(beta, jnp.float32)
    new_r = beta * r + (1.0 - beta) * _psum(rowsum[:m, 0], col_axes)
    new_c = beta * c + (1.0 - beta) * _psum(
        jnp.sum(colsum_parts[:, 0, :n], axis=0), row_axes)
    denom = jnp.maximum(_psum(jnp.sum(new_r), row_axes), cfg.eps_stat)
    if cfg.bias_correction:
        corr = jnp.maximum(
            1.0 - jnp.asarray(beta, jnp.float32)
            ** jnp.asarray(step, jnp.float32), cfg.eps_stat)
    else:
        corr = jnp.float32(1.0)
    inv_denom_corr = 1.0 / (denom * corr)
    lr_eff = jnp.asarray(lr, jnp.float32)
    decay = 1.0 - lr_eff * jnp.asarray(weight_decay, jnp.float32)
    # padded rows/cols of the moments are zero, so û is zero there
    r_col = jnp.pad(new_r, (0, p_p.shape[0] - m))[:, None]
    c_row = jnp.pad(new_c, (0, p_p.shape[1] - n))[None, :]
    if not (row_axes or col_axes):
        new_p = update_pallas(
            p_p, g_p, r_col, c_row, lr=lr_eff, inv_denom_corr=inv_denom_corr,
            eps_div=cfg.eps_div, clip=clip, eps_rms=cfg.eps_rms,
            n_elems=m * n, decay=decay, literal=cfg.literal_div_v,
            block=(bm, bn), interpret=interpret)
        return new_p[:m, :n], new_r, new_c
    # a shard: Σu² and Σθ² are the whole tensor's, summed across the mesh
    # between the sums pass and the apply pass
    uu, pp = sums_pallas(p_p, g_p, r_col, c_row,
                         inv_denom_corr=inv_denom_corr, eps_div=cfg.eps_div,
                         literal=cfg.literal_div_v, block=(bm, bn),
                         interpret=interpret)
    uu, pp = _psum((uu, pp), row_axes + col_axes)
    shards = math.prod(jax.lax.axis_size(a) for a in row_axes + col_axes)
    new_p = apply_pallas(
        p_p, g_p, r_col, c_row, uu, pp, lr=lr_eff,
        inv_denom_corr=inv_denom_corr, eps_div=cfg.eps_div, clip=clip,
        eps_rms=cfg.eps_rms, n_elems=m * n * shards, decay=decay,
        literal=cfg.literal_div_v, block=(bm, bn), interpret=interpret)
    return new_p[:m, :n], new_r, new_c
