"""Optimizer rules (Opt v2): AdaLomo + the baselines the paper compares to.

Every optimizer is an :class:`repro.core.api.UpdateRule`:

    rule.init(param, factored=None)          -> state
    rule.update(param, grad, state, hp, step) -> (new_param, new_state)

where ``hp`` is a resolved dict of *dynamic* hyperparameters (each rule
declares its accepted set + defaults in ``rule.hparams``) and ``step`` is
the 1-based global step as float32.  Wrap a rule in
:class:`repro.core.api.Opt` for whole-pytree init/step with param-group
labeling; the same rule runs (i) unfused via ``Opt.step``, (ii) fused into
the backward scan (``core/fused.py``), and — for AdaLomo — (iii) on the
Pallas TPU kernel via ``backend="pallas"``.  LOMO is literally ``sgd()``
under the fused engine; the paper's §2.2 ablations are ``sgd_momentum()``
(Eq. 3) and ``sgd_variance()`` (Eq. 4).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import adalomo as _adalomo
from repro.core.api import (GroupSpec, Opt, UpdateRule, make_rule,
                            no_decay_1d)

__all__ = ["adalomo", "sgd", "sgd_momentum", "sgd_variance", "adamw",
           "adafactor", "REGISTRY", "get_rule", "get_opt", "Opt",
           "GroupSpec", "UpdateRule", "no_decay_1d"]

Array = jax.Array


# --------------------------------------------------------------------------
# AdaLomo — one rule, two backends (pure jnp / Pallas kernel)
# --------------------------------------------------------------------------

_BACKENDS = ("auto", "jnp", "pallas")


def _resolve_backend(backend: str) -> str:
    if backend not in _BACKENDS:
        raise ValueError(f"backend {backend!r} not in {_BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def _on_shards(kernel: Callable, sharding) -> Callable:
    """The kernel on each device's shard of a tensor placed by ``sharding``
    (a ``NamedSharding``), inside ``shard_map`` with the parameter's own
    spec: θ and g in and θ' out as the shards they are, r with the rows,
    c with the columns, leading stack dims (MoE experts) as they lie.  The
    kernel adds its row and column sums and the grouped normalization's
    sums over the axes that split the last two dims, so each shard takes
    the whole tensor's update.  A ``pallas_call`` has no partitioner, so
    without a sharding a multi-device mesh in context (``jax.set_mesh``)
    runs it on a replicated spec: whole, on every device."""
    if sharding is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1:
            return kernel
        spec = P()
    else:
        mesh, spec = sharding.mesh, sharding.spec

    def run(param, grad, r, c, *scalars):
        parts = (tuple(spec) + (None,) * param.ndim)[:param.ndim]
        rows, cols = parts[-2:]
        on_shards = jax.shard_map(
            functools.partial(kernel, row_axes=_axes(rows),
                              col_axes=_axes(cols)),
            mesh=mesh,
            in_specs=(P(*parts), P(*parts), P(*parts[:-1]),
                      P(*parts[:-2], cols)) + (P(),) * len(scalars),
            out_specs=(P(*parts), P(*parts[:-1]), P(*parts[:-2], cols)),
            check_vma=False)
        return on_shards(param, grad, r, c,
                         *(jnp.asarray(x, jnp.float32) for x in scalars))

    return run


def _axes(entry) -> tuple:
    """The mesh axes of one dimension of a PartitionSpec."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def adalomo(cfg: Optional[_adalomo.AdaLomoConfig] = None, *,
            backend: str = "auto", interpret: bool = False,
            block: Optional[tuple] = None,
            lr: float = _adalomo.DEFAULT_HPARAMS["lr"],
            beta: float = _adalomo.DEFAULT_HPARAMS["beta"],
            weight_decay: float = _adalomo.DEFAULT_HPARAMS["weight_decay"],
            clip: float = _adalomo.DEFAULT_HPARAMS["clip"]) -> UpdateRule:
    """AdaLomo (paper Alg. 1) with backend dispatch.

    ``backend="pallas"`` routes factored ≥2-D tensors through the fused
    Pallas kernel (``kernels/adalomo_update``); 1-D/unfactored tensors and
    ``backend="jnp"`` use the pure-jnp path — same math, same state.
    ``"auto"`` picks pallas on TPU, jnp elsewhere.  ``interpret=True``
    runs the kernel in interpreter mode (CPU validation).
    ``lr``/``beta``/``weight_decay``/``clip`` set the rule's *default*
    dynamic hparams; call-time values override them without recompiling.
    ``sharding`` (the parameter's ``NamedSharding``, given by the fused
    step on a mesh) runs the kernel on each device's shard; without it a
    multi-device mesh in context runs the kernel whole on every device.
    """
    cfg = cfg or _adalomo.AdaLomoConfig()
    use_pallas = _resolve_backend(backend) == "pallas"
    if use_pallas:
        from repro.kernels.adalomo_update.ops import adalomo_update
        from repro.kernels.adalomo_update.adalomo_update import DEFAULT_BLOCK
        kblock = tuple(block) if block is not None else DEFAULT_BLOCK

    def init_fn(param, *, factored=None):
        c = cfg if factored is None else dataclasses.replace(
            cfg, factored=factored)
        return _adalomo.init_state(param, c)

    def update_fn(param, grad, state, hp, step, *, sharding=None):
        if use_pallas and state.v is None and param.ndim >= 2:
            kernel = functools.partial(adalomo_update, cfg=cfg, block=kblock,
                                       interpret=interpret)
            new_p, nr, nc = _on_shards(kernel, sharding)(
                param, grad, state.r, state.c, hp["lr"], step, hp["beta"],
                hp["weight_decay"], hp["clip"])
            return new_p, _adalomo.FactoredState(r=nr, c=nc, v=None)
        return _adalomo.update_tensor(
            param, grad, state, lr=hp["lr"], step=step, beta=hp["beta"],
            weight_decay=hp["weight_decay"], clip=hp["clip"], cfg=cfg)

    return make_rule("adalomo", init_fn, update_fn,
                     hparams=dict(lr=lr, beta=beta,
                                  weight_decay=weight_decay, clip=clip))


# --------------------------------------------------------------------------
# SGD family (paper Eq. 1, 3, 4) — LOMO is fused sgd()
# --------------------------------------------------------------------------

def sgd(*, lr: float = 1e-3) -> UpdateRule:
    """Plain SGD — the LOMO update rule (paper Eq. 1)."""

    def init_fn(param, *, factored=None):
        del factored
        return ()

    def update_fn(param, grad, state, hp, step):
        del step
        p32 = param.astype(jnp.float32)
        new_param = (p32 - hp["lr"] * grad.astype(jnp.float32)).astype(
            param.dtype)
        return new_param, state

    return make_rule("sgd", init_fn, update_fn, hparams=dict(lr=lr))


class MomentumState(NamedTuple):
    m: Array


def sgd_momentum(*, lr: float = 1e-3, beta1: float = 0.9,
                 bias_correction: bool = True) -> UpdateRule:
    """First-moment-only ablation (paper Eq. 3)."""

    def init_fn(param, *, factored=None):
        del factored
        return MomentumState(m=jnp.zeros(param.shape, jnp.float32))

    def update_fn(param, grad, state, hp, step):
        b1 = hp["beta1"]
        g32 = grad.astype(jnp.float32)
        m = b1 * state.m + (1.0 - b1) * g32
        m_hat = m / (1.0 - b1 ** step) if bias_correction else m
        p32 = param.astype(jnp.float32)
        return ((p32 - hp["lr"] * m_hat).astype(param.dtype),
                MomentumState(m=m))

    return make_rule("sgd_momentum", init_fn, update_fn,
                     hparams=dict(lr=lr, beta1=beta1))


class VarianceState(NamedTuple):
    v: Array


def sgd_variance(*, lr: float = 1e-3, beta2: float = 0.999,
                 eps: float = 1e-8,
                 bias_correction: bool = True) -> UpdateRule:
    """Second-moment-only ablation (paper Eq. 4) — the 'SGD with variance'
    curve in Fig. 1/6 that motivates AdaLomo."""

    def init_fn(param, *, factored=None):
        del factored
        return VarianceState(v=jnp.zeros(param.shape, jnp.float32))

    def update_fn(param, grad, state, hp, step):
        b2 = hp["beta2"]
        g32 = grad.astype(jnp.float32)
        v = b2 * state.v + (1.0 - b2) * jnp.square(g32)
        v_hat = v / (1.0 - b2 ** step) if bias_correction else v
        p32 = param.astype(jnp.float32)
        upd = g32 / (jnp.sqrt(v_hat) + hp["eps"])
        return ((p32 - hp["lr"] * upd).astype(param.dtype),
                VarianceState(v=v))

    return make_rule("sgd_variance", init_fn, update_fn,
                     hparams=dict(lr=lr, beta2=beta2, eps=eps))


# --------------------------------------------------------------------------
# AdamW (paper Eq. 2 + decoupled weight decay) — the de-facto baseline
# --------------------------------------------------------------------------

class AdamState(NamedTuple):
    m: Array
    v: Array


def adamw(*, lr: float = 1e-3, beta1: float = 0.9, beta2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.0) -> UpdateRule:
    def init_fn(param, *, factored=None):
        del factored
        return AdamState(m=jnp.zeros(param.shape, jnp.float32),
                         v=jnp.zeros(param.shape, jnp.float32))

    def update_fn(param, grad, state, hp, step):
        b1, b2 = hp["beta1"], hp["beta2"]
        g32 = grad.astype(jnp.float32)
        m = b1 * state.m + (1.0 - b1) * g32
        v = b2 * state.v + (1.0 - b2) * jnp.square(g32)
        m_hat = m / (1.0 - b1 ** step)
        v_hat = v / (1.0 - b2 ** step)
        p32 = param.astype(jnp.float32)
        p32 = p32 * (1.0 - hp["lr"] * hp["weight_decay"])
        upd = m_hat / (jnp.sqrt(v_hat) + hp["eps"])
        return ((p32 - hp["lr"] * upd).astype(param.dtype),
                AdamState(m=m, v=v))

    return make_rule("adamw", init_fn, update_fn,
                     hparams=dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                                  weight_decay=weight_decay))


# --------------------------------------------------------------------------
# Adafactor (Shazeer & Stern 2018) — the factored-moment baseline.
# AdaLomo's Table-1 claim: same-quality factored state, but grads are O(1)
# because the update happens inside the backward pass.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdafactorConfig:
    """Structural config; decay_rate/clip/weight_decay are dynamic hparams."""

    eps_stat: float = 1e-30
    eps_rms: float = 1e-3
    min_dim_size_to_factor: int = 16
    factored: bool = True
    relative_step_scale: bool = True  # multiply update by max(eps2, RMS(θ))


def adafactor(cfg: Optional[AdafactorConfig] = None, *, lr: float = 1e-3,
              decay_rate: float = 0.8, clip: float = 1.0,
              weight_decay: float = 0.0) -> UpdateRule:
    cfg = cfg or AdafactorConfig()
    # Reuse AdaLomo's factored-state container/init with matching thresholds.
    al_cfg = _adalomo.AdaLomoConfig(
        min_dim_size_to_factor=cfg.min_dim_size_to_factor,
        factored=cfg.factored, eps_stat=cfg.eps_stat)

    def init_fn(param, *, factored=None):
        c = al_cfg if factored is None else dataclasses.replace(
            al_cfg, factored=factored)
        return _adalomo.init_state(param, c)

    def update_fn(param, grad, state, hp, step):
        g32 = grad.astype(jnp.float32)
        g2 = jnp.square(g32) + cfg.eps_stat
        beta2t = 1.0 - step ** (-hp["decay_rate"])
        if state.v is not None:
            v = beta2t * state.v + (1.0 - beta2t) * g2
            new_state = _adalomo.FactoredState(r=None, c=None, v=v)
        else:
            r = beta2t * state.r + (1.0 - beta2t) * jnp.mean(g2, axis=-1)
            c = beta2t * state.c + (1.0 - beta2t) * jnp.mean(g2, axis=-2)
            new_state = _adalomo.FactoredState(r=r, c=c, v=None)
        v_hat = _adalomo.reconstruct_v(new_state, al_cfg)
        u = g32 * jax.lax.rsqrt(v_hat + cfg.eps_stat)
        axes = _adalomo._matrix_axes(u.ndim)
        rms_u = _adalomo._rms(u, axes)
        u = u / jnp.maximum(1.0, rms_u / hp["clip"])
        if cfg.relative_step_scale:
            u = u * jnp.maximum(cfg.eps_rms,
                                _adalomo._rms(param.astype(jnp.float32),
                                              axes))
        p32 = param.astype(jnp.float32)
        p32 = p32 * (1.0 - hp["lr"] * hp["weight_decay"])
        return (p32 - hp["lr"] * u).astype(param.dtype), new_state

    return make_rule("adafactor", init_fn, update_fn,
                     hparams=dict(lr=lr, decay_rate=decay_rate, clip=clip,
                                  weight_decay=weight_decay))


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

REGISTRY: dict[str, Callable[..., UpdateRule]] = {
    "adalomo": adalomo,
    "lomo": sgd,       # LOMO == fused SGD
    "sgd": sgd,
    "sgd_momentum": sgd_momentum,
    "sgd_variance": sgd_variance,
    "adamw": adamw,
    "adafactor": adafactor,
}


def _accepted_kwargs(factory) -> set[str]:
    sig = inspect.signature(factory)
    return {p.name for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)}


def get_rule(name: str, **kwargs) -> UpdateRule:
    """Build a rule by registry name; unknown kwargs raise a KeyError
    naming the kwargs this rule accepts (not a bare TypeError)."""
    if name not in REGISTRY:
        raise KeyError(f"unknown optimizer {name!r}; have {sorted(REGISTRY)}")
    factory = REGISTRY[name]
    accepted = _accepted_kwargs(factory)
    unknown = sorted(set(kwargs) - accepted)
    if unknown:
        raise KeyError(
            f"optimizer {name!r} does not accept {unknown}; accepted "
            f"kwargs: {sorted(accepted)} (dynamic hyperparameters can also "
            f"be passed per step via the hparams argument)")
    return factory(**kwargs)


def get_opt(name: str, *, groups: tuple = (), **kwargs) -> Opt:
    """``Opt(get_rule(name, **kwargs), groups)`` — the one-stop constructor."""
    return Opt(get_rule(name, **kwargs), groups=groups)
