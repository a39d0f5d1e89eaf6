"""Jitted wrapper for the fused AdaLomo update kernel.

``adalomo_update(param, grad, r, c, lr, step, beta, weight_decay, clip)``
— handles padding to block multiples, the O(m+n) moment EMA and r-sum
between the two kernels, leading stack dims via vmap, and exposes
``interpret=`` for CPU validation against ref.py.

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

import jax
import jax.numpy as jnp

from repro.core.adalomo import DEFAULT_HPARAMS, AdaLomoConfig
from repro.kernels.adalomo_update.adalomo_update import (
    DEFAULT_BLOCK, stats_pallas, update_pallas)


def _pad_to(x, bm, bn):
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(jax.jit, static_argnames=("cfg", "block", "interpret"))
def adalomo_update(param, grad, r, c, lr, step,
                   beta=DEFAULT_HPARAMS["beta"],
                   weight_decay=DEFAULT_HPARAMS["weight_decay"],
                   clip=DEFAULT_HPARAMS["clip"], *,
                   cfg: AdaLomoConfig = AdaLomoConfig(),
                   block=DEFAULT_BLOCK, interpret: bool = False):
    """Fused AdaLomo step for a 2-D tensor (or stacked [..., m, n] via vmap).

    Returns (new_param, new_r, new_c). Semantics == ref.adalomo_update_ref:
    decoupled weight decay scales θ at the final write, while the RMS(θ)
    trust scale is computed from the un-decayed θ.
    """
    if param.ndim > 2:
        fn = functools.partial(adalomo_update, cfg=cfg, block=block,
                               interpret=interpret)
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
    new_r = beta * r + (1.0 - beta) * rowsum[:m, 0]
    new_c = beta * c + (1.0 - beta) * jnp.sum(colsum_parts[:, 0, :n], axis=0)
    denom = jnp.maximum(jnp.sum(new_r), cfg.eps_stat)
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
    new_p = update_pallas(
        p_p, g_p, r_col, c_row, lr=lr_eff, inv_denom_corr=inv_denom_corr,
        eps_div=cfg.eps_div, clip=clip, eps_rms=cfg.eps_rms,
        n_elems=m * n, decay=decay, literal=cfg.literal_div_v,
        block=(bm, bn), interpret=interpret)
    return new_p[:m, :n], new_r, new_c
