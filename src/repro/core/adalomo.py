"""AdaLomo: low-memory optimization with adaptive learning rate.

Implements the paper's Algorithm 1 as pure per-tensor functions so the same
math is usable from three call-sites:

  * the fused-backward engine (``core/fused.py``) — applied per layer slice
    inside the reverse scan (the paper's LOMO-style fused update);
  * the tree-level optax-like API (``core/api.py``) — the unfused baseline;
  * the Pallas kernel (``kernels/adalomo_update``) — whose ``ref.py`` oracle
    is literally :func:`compute_update` below.

State per m×n parameter is the non-negative-matrix-factorized second moment
(r ∈ R^m, c ∈ R^n), per paper Eq. (5)-(7):

    r_t = β r_{t-1} + (1-β) rowsum(g²)
    c_t = β c_{t-1} + (1-β) colsum(g²)
    v_t = outer(r_t, c_t) / sum(r_t)

followed by the grouped update normalization of Alg. 1 line 11:

    u  = g / (sqrt(v̂) + ε)           # see DESIGN.md on the line-10 typo
    û  = u / max(1, RMS(u)/d) * max(ε₂, RMS(θ))
    θ ← θ - α û

1-D parameters (norm scales, biases) keep the unfactored v (already O(m)).
Leading dimensions beyond the trailing matrix dims (stacked layers ``[L,m,n]``,
experts ``[E,m,n]``) are treated as independent parameter groups: statistics
and RMS reductions are over the trailing matrix dims only, so behaviour is
identical whether a layer stack is updated as one array or slice-by-slice.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class AdaLomoConfig:
    """*Structural* configuration of AdaLomo (paper §3.1 / Alg. 1).

    Only knobs that change state layout, numerics structure, or algorithm
    shape live here.  The *dynamic* hyperparameters — lr, β, weight decay,
    clip threshold d — are call-time arguments (see DEFAULT_HPARAMS and the
    Opt v2 contract in ``core/api.py``), so they can be scheduled per step
    and overridden per param group with zero recompiles.
    """

    eps_div: float = 1e-8          # ε added to sqrt(v̂) in the division
    eps_stat: float = 1e-30        # tiny floor inside the statistics
    eps_rms: float = 1e-3          # ε₂: floor of the parameter-scale term
    min_dim_size_to_factor: int = 16
    factored: bool = True
    bias_correction: bool = True
    # Faithfulness switch: Alg.1 line 10 literally reads u = g / v (no sqrt).
    # Dimensionally inconsistent with Eq.(2)/(4); off by default (DESIGN.md).
    literal_div_v: bool = False
    # dtype for the factored statistics; fp32 regardless of param dtype.
    state_dtype: Any = jnp.float32


# Dynamic hyperparameters (Opt v2): accepted keys and paper defaults.
#   beta — single decay coefficient β for r and c (paper Eq. 6/7)
#   clip — d in  max(1, RMS(u)/d)  (Alg. 1 line 11)
#   weight_decay — decoupled, paper default: none
DEFAULT_HPARAMS = {"lr": 1e-3, "beta": 0.999, "weight_decay": 0.0,
                   "clip": 1.0}


class FactoredState(NamedTuple):
    """Second-moment state for one tensor: (r, c) if factored else v."""

    r: Optional[Array]
    c: Optional[Array]
    v: Optional[Array]

    def pspecs(self, spec: P) -> "FactoredState":
        """This state's PartitionSpecs for a parameter placed by ``spec``
        (one entry per parameter dim): r with the rows it sums (the last
        dim dropped), c with the columns (the second-to-last dropped), v
        as the parameter."""
        parts = tuple(spec)
        return FactoredState(
            r=None if self.r is None else P(*parts[:-1]),
            c=None if self.c is None else P(*parts[:-2], parts[-1]),
            v=None if self.v is None else P(*parts))


def _should_factor(shape: tuple[int, ...], cfg: AdaLomoConfig) -> bool:
    if not cfg.factored or len(shape) < 2:
        return False
    m, n = shape[-2], shape[-1]
    return min(m, n) >= cfg.min_dim_size_to_factor


def init_state(param: Array, cfg: AdaLomoConfig) -> FactoredState:
    """O(m+n) state for an m×n tensor; O(m) unfactored state otherwise."""
    shape = tuple(param.shape)
    dt = cfg.state_dtype
    if _should_factor(shape, cfg):
        r = jnp.zeros(shape[:-1], dtype=dt)            # (..., m)
        c = jnp.zeros(shape[:-2] + shape[-1:], dtype=dt)  # (..., n)
        return FactoredState(r=r, c=c, v=None)
    return FactoredState(r=None, c=None, v=jnp.zeros(shape, dtype=dt))


def state_bytes(param: Array, cfg: AdaLomoConfig) -> int:
    """Analytic optimizer-state footprint (for the Table-1 benchmark)."""
    st = jax.eval_shape(lambda p: init_state(p, cfg), param)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st))


def _matrix_axes(ndim: int) -> tuple[int, ...]:
    """Axes forming 'the parameter matrix' — trailing two (or one if 1-D)."""
    return (-1,) if ndim < 2 else (-2, -1)


def _rms(x: Array, axes: tuple[int, ...]) -> Array:
    return jnp.sqrt(jnp.mean(jnp.square(x), axis=axes, keepdims=True))


def update_moment(
    grad: Array, state: FactoredState, *, beta, cfg: AdaLomoConfig
) -> FactoredState:
    """EMA update of the (possibly factored) second moment. Paper Eq.(6)(7)."""
    g2 = jnp.square(grad.astype(cfg.state_dtype)) + cfg.eps_stat
    b = beta
    if state.v is not None:
        return FactoredState(r=None, c=None, v=b * state.v + (1.0 - b) * g2)
    r = b * state.r + (1.0 - b) * jnp.sum(g2, axis=-1)
    c = b * state.c + (1.0 - b) * jnp.sum(g2, axis=-2)
    return FactoredState(r=r, c=c, v=None)


def reconstruct_v(state: FactoredState, cfg: AdaLomoConfig) -> Array:
    """v = outer(r, c) / sum(r) — rank-1 NMF reconstruction, paper Eq.(5)."""
    if state.v is not None:
        return state.v
    denom = jnp.sum(state.r, axis=-1, keepdims=True)  # (..., 1)
    # (..., m, 1) * (..., 1, n) / (..., 1, 1)
    return (state.r[..., :, None] * state.c[..., None, :]) / jnp.maximum(
        denom[..., None], cfg.eps_stat
    )


def compute_update(
    param: Array,
    grad: Array,
    state: FactoredState,
    *,
    step: Array,
    beta=DEFAULT_HPARAMS["beta"],
    clip=DEFAULT_HPARAMS["clip"],
    cfg: AdaLomoConfig,
) -> tuple[Array, FactoredState]:
    """Return (û, new_state): the grouped-normalized update of Alg. 1.

    ``step`` is the 1-based global step (scalar, for bias correction).
    ``beta``/``clip`` may be traced scalars (scheduled per call).
    û is in fp32; the caller applies ``θ ← θ - lr·û`` (and weight decay).
    """
    new_state = update_moment(grad, state, beta=beta, cfg=cfg)
    v = reconstruct_v(new_state, cfg)
    if cfg.bias_correction:
        correction = 1.0 - jnp.asarray(beta, cfg.state_dtype) \
            ** step.astype(cfg.state_dtype)
        v_hat = v / jnp.maximum(correction, cfg.eps_stat)
    else:
        v_hat = v
    g32 = grad.astype(cfg.state_dtype)
    if cfg.literal_div_v:  # Alg.1 line 10 verbatim (see DESIGN.md)
        u = g32 / (v_hat + cfg.eps_div)
    else:
        u = g32 / (jnp.sqrt(v_hat) + cfg.eps_div)
    axes = _matrix_axes(u.ndim)
    # Grouped update normalization (Alg.1 line 11): per-matrix trust ratio.
    rms_u = _rms(u, axes)
    u = u / jnp.maximum(1.0, rms_u / clip)
    p32 = param.astype(cfg.state_dtype)
    scale = jnp.maximum(cfg.eps_rms, _rms(p32, axes))
    u = u * scale
    return u, new_state


def update_tensor(
    param: Array,
    grad: Array,
    state: FactoredState,
    *,
    lr: Array,
    step: Array,
    beta=DEFAULT_HPARAMS["beta"],
    weight_decay=DEFAULT_HPARAMS["weight_decay"],
    clip=DEFAULT_HPARAMS["clip"],
    cfg: AdaLomoConfig,
) -> tuple[Array, FactoredState]:
    """One AdaLomo step for a single tensor: θ ← θ - α·û (Alg.1 line 12).

    Decoupled weight decay pre-scales θ, but the RMS(θ) trust scale inside
    ``compute_update`` is taken from the *un-decayed* θ (the Pallas kernel
    matches this — see tests/kernels parity with weight_decay > 0).
    Applied unconditionally: with weight_decay == 0 the factor is exactly
    1.0, so the no-decay path is bitwise unchanged.
    """
    u, new_state = compute_update(param, grad, state, step=step, beta=beta,
                                  clip=clip, cfg=cfg)
    p32 = param.astype(cfg.state_dtype)
    p32 = p32 * (1.0 - lr * weight_decay)
    new_param = (p32 - lr * u).astype(param.dtype)
    return new_param, new_state
