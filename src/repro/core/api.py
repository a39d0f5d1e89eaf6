"""Opt v2 — one composable, introspectable optimizer API.

The contract ("hyperparameters as arguments, state as data", DESIGN.md):

    opt   = Opt(rule, groups=(GroupSpec(...), ...))
    state = opt.init(params)                         # OptState: a pytree
    new_p, new_state = opt.step(params, grads, state, hparams)

* **Hyperparameters are call-time data.**  ``hparams`` is a plain dict of
  scalars — ``{"lr": ..., "beta": ..., "weight_decay": ..., ...}`` — passed
  on every step.  Values may be traced arrays, so schedules (lr, β, decay
  warmup) never trigger a recompile; the dict's *structure* is the only
  thing baked into the jaxpr.  A bare scalar is shorthand for
  ``{"lr": scalar}``.  Per-group overrides ride along under a ``"groups"``
  key: ``{"lr": 1e-3, "groups": {"embed": {"lr": 1e-4}}}``.

* **State is data.**  ``OptState(step, moments)`` holds one global step
  scalar and a moments pytree mirroring ``params`` — no closures, no
  hidden Python state, directly serializable by ``checkpoint/manager.py``
  and shardable by ``sharding/rules.py``.  The same layout is produced and
  consumed by the fused backward engine (``core/fused.py``), the unfused
  ``Opt.step`` path, and the Pallas kernel backend.

* **Param groups are path labels.**  A :class:`GroupSpec` maps leaves to a
  group by regex on the leaf's path string or by predicate on its
  :class:`LeafInfo`; each group carries default hparam overrides (e.g.
  ``weight_decay=0`` for norm scales and biases — the paper's grouped
  treatment) and an optional ``factored`` state mask.

Layout convention: a top-level ``"stacks"`` key marks scan-over-layers
parameter stacks ``[L, ...]`` (see ``core/fused.py``); their optimizer
state is initialized per layer slice (vmapped), so factorization and the
grouped-RMS axes see the per-layer tensor shape.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
import re
from typing import Any, Callable, Mapping, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

Array = jax.Array

# Top-level pytree key marking [L, ...] layer stacks (core/fused.py layout).
STACKS_KEY = "stacks"


# --------------------------------------------------------------------------
# Per-tensor rules: pure init/update with hyperparameters as data
# --------------------------------------------------------------------------

class UpdateRule(NamedTuple):
    """A per-tensor optimizer rule, v2.

    ``init(param, factored=None) -> state`` — per-tensor state (a pytree).
    ``update(param, grad, state, hp, step, *, sharding=None) ->
    (new_param, new_state)`` — one step; ``hp`` is a fully-resolved dict
    containing every key in ``hparams``; ``step`` is the 1-based global
    step as float32; ``sharding`` is the parameter's ``NamedSharding`` on
    a mesh (the fused step passes it there), which a rule that runs a
    kernel without a partitioner needs and any other rule ignores.
    ``hparams`` declares the accepted dynamic hyperparameters and their
    defaults — the introspection surface for schedules and group overrides.
    """

    name: str
    init: Callable[..., Any]
    update: Callable[..., tuple[Array, Any]]
    hparams: dict
    # Analytic per-tensor optimizer-state bytes (Table-1 benchmark).
    state_bytes: Callable[[Array], int]


def make_rule(name: str, init_fn, update_fn, hparams: Mapping[str, Any]
              ) -> UpdateRule:
    """Assemble an :class:`UpdateRule`, deriving ``state_bytes`` from init.
    An ``update_fn`` without a ``sharding`` keyword (elementwise, or left
    to XLA's partitioner) is given one it ignores."""
    if "sharding" not in inspect.signature(update_fn).parameters:
        plain = update_fn

        def update_fn(param, grad, state, hp, step, *, sharding=None):
            del sharding
            return plain(param, grad, state, hp, step)

    def state_bytes(param: Array) -> int:
        st = jax.eval_shape(lambda p: init_fn(p), param)
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st))

    return UpdateRule(name=name, init=init_fn, update=update_fn,
                      hparams=dict(hparams), state_bytes=state_bytes)


class OptState(NamedTuple):
    """Whole-tree optimizer state: ONE step scalar + per-tensor moments."""

    step: Array            # scalar int32, 1-based after first update
    moments: Any           # pytree matching params, of per-tensor states


# --------------------------------------------------------------------------
# Path-based param-group labeling
# --------------------------------------------------------------------------

def _key_name(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def path_str(key_path) -> str:
    """'outer/embed' / 'stacks/blocks/w_qkv' — the string GroupSpec regexes
    match against."""
    return "/".join(_key_name(k) for k in key_path)


@dataclasses.dataclass(frozen=True)
class LeafInfo:
    """What a group predicate gets to see about one parameter leaf."""

    path: str
    shape: tuple
    stacked: bool    # leading dim is a layer-stack axis ("stacks" subtree)

    @property
    def tensor_shape(self) -> tuple:
        """Shape of the per-tensor unit the rule sees (stack dim stripped)."""
        return self.shape[1:] if self.stacked else self.shape

    @property
    def tensor_ndim(self) -> int:
        return len(self.tensor_shape)


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """One param group: match rule + hparam overrides + state masks.

    ``match`` is a regex (``re.search`` on the leaf's path string) or a
    predicate ``f(LeafInfo) -> bool``.  The first matching GroupSpec wins;
    unmatched leaves belong to the default group (base hparams).
    ``hparams`` are static default overrides (validated against the rule's
    accepted set); call-time overrides via ``hparams["groups"][name]`` take
    precedence.  ``factored=False`` forces unfactored second-moment state
    for rules with factored state (a per-group state-layout mask).
    """

    name: str
    match: Union[str, Callable[[LeafInfo], bool]]
    hparams: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    factored: Optional[bool] = None

    def matches(self, info: LeafInfo) -> bool:
        if callable(self.match):
            return bool(self.match(info))
        return re.search(self.match, info.path) is not None


def no_decay_1d(name: str = "no_decay") -> GroupSpec:
    """The table-stakes AdamW grouping: no weight decay on 1-D tensors
    (norm scales, biases) — per-tensor ndim, so a [L, d] stacked norm
    scale counts as 1-D."""
    return GroupSpec(name, match=lambda i: i.tensor_ndim <= 1,
                     hparams={"weight_decay": 0.0})


def _leaf_info(key_path, leaf) -> LeafInfo:
    p = path_str(key_path)
    parts = p.split("/") if p else []
    stacked = (len(parts) >= 1 and parts[0] == STACKS_KEY
               and getattr(leaf, "ndim", 0) >= 1)
    return LeafInfo(path=p, shape=tuple(leaf.shape), stacked=stacked)


def _check_hparam_keys(rule: UpdateRule, d: Mapping, what: str) -> None:
    unknown = sorted(set(d) - set(rule.hparams))
    if unknown:
        raise KeyError(
            f"rule {rule.name!r} does not accept {what} {unknown}; "
            f"accepted hyperparameters: {sorted(rule.hparams)}")


# --------------------------------------------------------------------------
# The optimizer object
# --------------------------------------------------------------------------

class Opt:
    """A per-tensor rule + param groups = a whole-pytree optimizer.

    One instance drives the unfused path (:meth:`step`), the fused
    backward engine (``core/fused.py`` consumes ``rule``/``labels``/
    ``resolve``), and — through the rule's backend dispatch — the Pallas
    kernel, all over the same :class:`OptState` layout.
    """

    def __init__(self, rule: UpdateRule, groups: tuple = ()):
        names = [g.name for g in groups]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate group names: {names}")
        for g in groups:
            _check_hparam_keys(rule, g.hparams, f"group {g.name!r} hparams")
        self.rule = rule
        self.groups = tuple(groups)

    @property
    def name(self) -> str:
        return self.rule.name

    # ---------------- labeling & hparam resolution ----------------
    def _flat_infos(self, params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        infos = [_leaf_info(kp, leaf) for kp, leaf in flat]
        labels = []
        for info in infos:
            idx = 0
            for i, g in enumerate(self.groups):
                if g.matches(info):
                    idx = i + 1
                    break
            labels.append(idx)
        return flat, treedef, infos, labels

    def labels(self, params):
        """Pytree of group indices (0 = default, i+1 = groups[i]) matching
        ``params`` — the introspectable label assignment."""
        _, treedef, _, labels = self._flat_infos(params)
        return jax.tree_util.tree_unflatten(treedef, labels)

    def resolve(self, hparams=None) -> tuple:
        """Resolved per-group hparam dicts, indexed by label.

        Merge order (later wins): rule defaults < call-time base <
        GroupSpec static overrides < call-time ``hparams["groups"][name]``.
        Unknown keys raise a KeyError naming the accepted set.
        """
        if hparams is None:
            hparams = {}
        if not isinstance(hparams, Mapping):
            hparams = {"lr": hparams}
        user = dict(hparams)
        group_over = dict(user.pop("groups", None) or {})
        _check_hparam_keys(self.rule, user, "hparams")
        known = {g.name for g in self.groups}
        unknown_groups = sorted(set(group_over) - known)
        if unknown_groups:
            raise KeyError(f"unknown group overrides {unknown_groups}; "
                           f"groups: {sorted(known)}")
        base = {**self.rule.hparams, **user}
        out = [base]
        for g in self.groups:
            over = dict(group_over.get(g.name, {}))
            _check_hparam_keys(self.rule, over,
                               f"group {g.name!r} call-time hparams")
            out.append({**base, **g.hparams, **over})
        return tuple(out)

    def _group_of(self, label: int) -> Optional[GroupSpec]:
        return None if label == 0 else self.groups[label - 1]

    # ---------------- init / step ----------------
    def init(self, params) -> OptState:
        """Per-tensor state for every leaf; ``stacks`` leaves vmapped so
        state[i] == rule.init(param[i]) (factorization and grouped-RMS axes
        see the per-layer shape)."""
        flat, treedef, infos, labels = self._flat_infos(params)
        moments = []
        for (kp, leaf), info, lab in zip(flat, infos, labels):
            g = self._group_of(lab)
            factored = g.factored if g is not None else None
            if info.stacked:
                st = jax.vmap(
                    lambda p: self.rule.init(p, factored=factored))(leaf)
            else:
                st = self.rule.init(leaf, factored=factored)
            moments.append(st)
        return OptState(step=jnp.zeros((), jnp.int32),
                        moments=jax.tree_util.tree_unflatten(treedef,
                                                             moments))

    def step(self, params, grads, state: OptState, hparams=None
             ) -> tuple[Any, OptState]:
        """One unfused optimizer step: θ, s ← rule(θ, g, s, hp) per tensor,
        vmapping over the layer dim of ``stacks`` leaves so the math is
        identical to the fused path."""
        hp = self.resolve(hparams)
        flat, treedef, infos, labels = self._flat_infos(params)
        g_flat = treedef.flatten_up_to(grads)
        s_flat = treedef.flatten_up_to(state.moments)
        new_step = state.step + 1
        stepf = new_step.astype(jnp.float32)
        new_p, new_s = [], []
        for (kp, p), g, s, info, lab in zip(flat, g_flat, s_flat, infos,
                                            labels):
            d = hp[lab]
            if info.stacked:
                p2, s2 = jax.vmap(
                    lambda pi, gi, si: self.rule.update(pi, gi, si, d,
                                                        stepf))(p, g, s)
            else:
                p2, s2 = self.rule.update(p, g, s, d, stepf)
            new_p.append(p2)
            new_s.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                OptState(step=new_step,
                         moments=jax.tree_util.tree_unflatten(treedef,
                                                              new_s)))

    # ---------------- introspection ----------------
    def state_bytes(self, params) -> int:
        """Analytic optimizer-state footprint, honoring group state masks
        (Table-1 accounting)."""
        st = jax.eval_shape(self.init, params)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(st.moments))

    def describe(self, params) -> dict:
        """Per-group accounting: leaf paths, param counts, hparam defaults."""
        flat, _, infos, labels = self._flat_infos(params)
        hp = self.resolve()
        out = {}
        for lab, name in enumerate(
                ["default"] + [g.name for g in self.groups]):
            leaves = [info for info, l_ in zip(infos, labels) if l_ == lab]
            out[name] = {
                "paths": [i.path for i in leaves],
                "n_params": sum(math.prod(i.shape) for i in leaves),
                "hparams": {k: float(v) for k, v in hp[lab].items()},
            }
        return out
