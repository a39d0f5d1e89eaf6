"""Partition rules: param/optimizer/activation shardings for pjit.

Logical axes:
  * ``dp`` — data parallel + ZeRO-3/FSDP param sharding.  Resolves to
    ``('data',)`` on the single-pod mesh and ``('pod','data')`` multi-pod
    for the *batch*; parameters are FSDP-sharded over ``'data'`` only
    (gathered within a pod; replicated across pods — all-gathering weights
    over the inter-pod DCI every layer would dominate the step).
  * ``tp`` — tensor/expert parallel, resolves to ``('model',)``.

Rules are (regex over the param path, dim-role template) pairs; every rule
is shape-guarded: an axis is applied to a dim only if the dim is divisible
by the mesh axis size (e.g. whisper's vocab 51865 falls back to replicated
instead of failing).  Optimizer state shardings are derived from each
param's own spec, so AdaLomo's factored (r, c) vectors land on the same
devices as the rows/columns they describe.
"""
from __future__ import annotations

import math
import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


class MeshAxes:
    """Resolved logical→physical axis names for a given mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        names = mesh.axis_names
        self.batch = tuple(n for n in ("pod", "data") if n in names)
        self.fsdp = ("data",) if "data" in names else ()
        self.tp = ("model",) if "model" in names else ()

    def size(self, axes: tuple[str, ...]) -> int:
        return math.prod(self.mesh.shape[a] for a in axes) if axes else 1


# Dim-role templates per param-name pattern.  Roles:
#   'fsdp' → shard over data axis (ZeRO-3);  'tp' → tensor/expert parallel;
#   None → replicated;  'stack' → leading layer/stack dim (never sharded).
# Matched against the '/'-joined tree path, most-specific first.
_PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    # --- MoE expert weights [E, d, f] / [E, f, d]: EP over tp, FSDP inner
    (r"moe/w_(gate|up)$", ("tp", "fsdp", None)),
    (r"moe/w_down$", ("tp", None, "fsdp")),
    (r"moe/router$", ("fsdp", None)),
    (r"moe/shared_mlp/w_(gate|up)$", ("fsdp", "tp")),
    (r"moe/shared_mlp/w_down$", ("tp", "fsdp")),
    # --- attention projections
    (r"attn/w[qkv]$", ("fsdp", "tp")),
    (r"attn/wo$", ("tp", "fsdp")),
    (r"attn/w_dq$", ("fsdp", "tp")),
    (r"attn/w_uq$", ("tp", None)),        # q_lora sharded out of w_dq
    (r"attn/w_dkv$", ("fsdp", None)),     # latent stays replicated (512)
    (r"attn/w_kr$", ("fsdp", None)),
    (r"attn/w_u[kv]$", (None, "tp")),     # per-head up-proj over tp
    (r"(self_attn|cross_attn)/w[qkv]$", ("fsdp", "tp")),
    (r"(self_attn|cross_attn)/wo$", ("tp", "fsdp")),
    # --- dense MLP
    (r"mlp/w_(gate|up)$", ("fsdp", "tp")),
    (r"mlp/w_down$", ("tp", "fsdp")),
    # --- zamba2 shared block + lora
    (r"^shared/w[qkv]$", ("fsdp", "tp")),
    (r"^shared/wo$", ("tp", "fsdp")),
    (r"^shared/w_(gate|up)$", ("fsdp", "tp")),
    (r"^shared/w_down$", ("tp", "fsdp")),
    (r"lora_[qkv]A$", ("fsdp", None)),
    (r"lora_[qkv]B$", (None, "tp")),
    # --- mamba2
    (r"in_proj$", ("fsdp", "tp")),
    (r"out_proj$", ("tp", "fsdp")),
    (r"conv_w$", ("tp", None)),
    (r"conv_b$", ("tp",)),
    # --- embeddings / head
    (r"tok_embed$", ("tp", "fsdp")),
    (r"head$", ("fsdp", "tp")),
    (r"mtp_proj$", ("fsdp", "tp")),
    # --- everything else (norm scales, biases, A_log, D, dt_bias): replicated
]


def _spec_for_shape(shape: tuple[int, ...], roles: tuple[Optional[str], ...],
                    axes: MeshAxes) -> P:
    """Apply role template to a shape, right-aligned (leading dims = stack)."""
    n_stack = len(shape) - len(roles)
    spec: list = [None] * len(shape)
    for i, role in enumerate(roles):
        dim = n_stack + i
        if dim < 0 or role is None:
            continue
        ax = {"fsdp": axes.fsdp, "tp": axes.tp}[role]
        if ax and shape[dim] % axes.size(ax) == 0 and shape[dim] > 1:
            spec[dim] = ax if len(ax) > 1 else ax[0]
    return P(*spec)


def param_pspecs(params, axes: MeshAxes):
    """PartitionSpec pytree matching ``params``."""
    def leaf_spec(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        for pat, roles in _PARAM_RULES:
            if re.search(pat, pstr):
                if len(leaf.shape) < len(roles):
                    # e.g. 1-D bias matched by a 2-D rule: replicate
                    return P()
                return _spec_for_shape(tuple(leaf.shape), roles, axes)
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def opt_pspecs(opt_state, params, param_specs, axes: MeshAxes):
    """Optimizer-state specs, each parameter's state derived from that
    parameter's own spec (``opt_state.moments`` mirrors ``params``; the
    step counter is replicated).

    A state that knows how it lies with its parameter says so
    (``pspecs(spec)``: AdaLomo's factored r with the rows it describes, c
    with the columns); any other state leaf of the parameter's shape
    (Adam m/v) inherits the full spec, and the rest is replicated.
    """
    del axes

    def for_param(param, spec, state):
        spec = P(*(tuple(spec) + (None,) * param.ndim)[:param.ndim])
        if hasattr(state, "pspecs"):
            return state.pspecs(spec)
        return jax.tree.map(
            lambda leaf: spec if leaf.shape == param.shape else P(), state)

    moments = jax.tree.map(for_param, params, param_specs,
                           opt_state.moments)
    return opt_state._replace(
        step=jax.tree.map(lambda _: P(), opt_state.step), moments=moments)


def batch_pspecs(batch, axes: MeshAxes):
    """Shard the leading (batch) dim of every input over dp axes."""
    ba = axes.batch if len(axes.batch) > 1 else (
        axes.batch[0] if axes.batch else None)

    def leaf_spec(leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] % axes.size(axes.batch) == 0 and leaf.shape[0] > 1:
            return P(ba, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree.map(leaf_spec, batch)


def cache_pspecs(cache, axes: MeshAxes, batch_size: int):
    """KV/state caches: batch over dp when divisible; cache length (axis 2
    of [L,B,W,...] tensors) over tp for long-context cells; otherwise the
    KV-head/state dims stay local."""
    dp_size = axes.size(axes.batch)
    tp_size = axes.size(axes.tp)
    ba = axes.batch if len(axes.batch) > 1 else (
        axes.batch[0] if axes.batch else None)
    tpa = axes.tp[0] if axes.tp else None

    def leaf_spec(leaf):
        if leaf.ndim <= 1:
            return P()
        spec: list = [None] * leaf.ndim
        # [L, B, W, ...] layout: axis 1 = batch, axis 2 = window/length
        if leaf.ndim >= 3 and leaf.shape[1] == batch_size:
            if batch_size % dp_size == 0 and batch_size > 1:
                spec[1] = ba
            if tpa and leaf.shape[2] % tp_size == 0 and leaf.shape[2] > 1:
                spec[2] = tpa
        elif leaf.shape[0] == batch_size and batch_size % dp_size == 0 \
                and batch_size > 1:
            spec[0] = ba
        return P(*spec)

    return jax.tree.map(leaf_spec, cache)


def _reshard_use(x, use_sh: NamedSharding, grad_sh: NamedSharding):
    """Identity with asymmetric sharding: the primal is constrained to the
    use-sharding (forcing a *bf16* all-gather of the resting ZeRO-3 shard
    before any dtype legalization can upcast it), while the cotangent is
    constrained straight to the resting sharding (a reduce-scatter instead
    of the default full all-reduce).  §Perf H3/H4."""

    @jax.custom_vjp
    def f(v):
        return jax.lax.with_sharding_constraint(v, use_sh)

    def fwd(v):
        return f(v), None

    def bwd(_, g):
        return (jax.lax.with_sharding_constraint(g, grad_sh),)

    f.defvjp(fwd, bwd)
    return f(x)


def make_param_constraint(mesh: Mesh, axes: MeshAxes, params):
    """Transient weight gather for the fused scan (ZeRO-3 'use' path).

    Per layer slice: dense/attention weights are gathered to full
    replication for the duration of the layer (their resting state stays
    256-way sharded); MoE expert tensors keep their expert-parallel 'tp'
    dim (never gathered — 11 GB/layer for deepseek-v3).  Gradients
    reduce-scatter back to the resting sharding via the custom vjp.

    Returns ``fn(stack_name) -> (layer_params -> layer_params)``.
    """
    specs = param_pspecs(params, axes)

    def for_stack(stack_name: str):
        sub = specs["stacks"][stack_name]

        def leaf_plan(path, spec):
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            rest = list(spec)[1:]  # strip stacked layer dim
            if re.search(r"moe/w_(gate|up|down)", pstr):
                # keep EP axis, drop only fsdp axes
                use = [a if a and set(_as_tuple(a)) <= set(axes.tp) else None
                       for a in rest]
            else:
                use = [None] * len(rest)
            return (NamedSharding(mesh, P(*use)),
                    NamedSharding(mesh, P(*rest)))

        plans = jax.tree_util.tree_map_with_path(
            leaf_plan, sub, is_leaf=lambda x: isinstance(x, P))

        def constrain(layer_p):
            return _apply_plans(layer_p, plans)

        return constrain

    return for_stack


def _apply_plans(layer_p, plans):
    treedef = jax.tree.structure(layer_p)
    leaves = treedef.flatten_up_to(layer_p)
    plan_leaves = treedef.flatten_up_to(plans)
    out = [_reshard_use(v, u, g) for v, (u, g) in zip(leaves, plan_leaves)]
    return treedef.unflatten(out)


def _as_tuple(a):
    return a if isinstance(a, tuple) else (a,)


def make_grad_constraint(mesh: Mesh, axes: MeshAxes, params):
    """Gradient shardings (§Perf H2) for the fused step: each gradient is
    constrained to its parameter's sharding before the optimizer consumes
    it, which turns the fp32 full-tensor all-reduce of dW into a
    reduce-scatter; the rule, told the same sharding, updates each
    device's shard, and the factored (r,c) statistics cost only O(m+n)
    cross-shard traffic.

    Returns ``fn(group) -> NamedSharding tree``: for a stack name, the
    shardings of one layer's slice (the leading layer dim stripped); for
    ``"outer"`` or ``"shared"``, those of the leaves as they are.
    """
    specs = param_pspecs(params, axes)

    def slice_sharding(spec: P):
        # strip the leading (layer) dim of the stacked spec
        parts = list(spec)
        return NamedSharding(mesh, P(*parts[1:]) if parts else P())

    def for_group(name: str):
        if name in specs["stacks"]:
            return jax.tree.map(slice_sharding, specs["stacks"][name],
                                is_leaf=lambda x: isinstance(x, P))
        return to_shardings(specs[name], mesh)

    return for_group


def make_residual_constraint(mesh: Mesh, axes: MeshAxes):
    """Sequence-shard saved layer-input residuals: [B, S, d] → P(dp, tp, ∅).

    This is what keeps fused-backward activation memory on-chip at
    train_4k×global-batch-256 scale (DESIGN.md §2); XLA inserts
    reduce-scatter/all-gather pairs around the saved values.
    """
    ba = axes.batch if len(axes.batch) > 1 else (
        axes.batch[0] if axes.batch else None)
    tpa = axes.tp[0] if axes.tp else None
    dp_size = axes.size(axes.batch)
    tp_size = axes.size(axes.tp)

    def constrain(x):
        def leaf(v):
            if not hasattr(v, "ndim") or v.ndim < 3:
                return v
            spec: list = [None] * v.ndim
            if v.shape[0] % dp_size == 0 and v.shape[0] > 1:
                spec[0] = ba
            if tpa and v.shape[1] % tp_size == 0 and v.shape[1] > 1:
                spec[1] = tpa
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(*spec)))
        return jax.tree.map(leaf, x)

    return constrain


def to_shardings(pspec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
