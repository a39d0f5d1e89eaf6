"""Fused backward-and-update engine: LOMO's mechanism, TPU/XLA-native.

The paper's LOMO/AdaLomo fuses the optimizer step into the backward pass so
that no more than ~one layer's gradients are ever resident (O(1) gradient
memory in depth).  PyTorch does this with eager autograd hooks; XLA has no
hooks, so we express the same dataflow *structurally*:

  * models are scan-over-layers with stacked ``[L, ...]`` parameter pytrees;
  * the forward pass is a ``lax.scan`` that saves each layer's *input*
    (residual) — nothing else;
  * the backward pass is a **reverse ``lax.scan``** whose body
      1. re-runs one layer's forward under ``jax.vjp`` (per-layer remat),
      2. obtains that layer's parameter gradients,
      3. applies the optimizer rule to that layer *immediately*,
      4. carries only the activation gradient (and small shared-param
         gradient accumulators) to the next iteration.

  The parameter gradient of layer ℓ is born and dies inside one scan
  iteration — the direct analogue of LOMO's "gradients of only two
  consecutive parameters are live".  With (params, opt_state) donated at the
  jit boundary, XLA updates buffers in place.

Each phase carries a named scope (``fwd``; ``bwd`` with ``recompute``,
``grad`` and ``update`` inside; ``head`` for embedding, loss and the
outer update), which a profiler trace reports per device op.

Grouped update normalization (paper §3.2) is what makes this a *single*
backward pass: the trust-ratio normalization in the rule needs only the
layer-local tensors, never a global gradient norm.  ``global_grad_norm``
mode below reproduces LOMO's two-pass alternative for the Appendix-B
benchmark.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.api import Opt, OptState, UpdateRule

Array = jax.Array


# --------------------------------------------------------------------------
# Per-tensor rule application across an arbitrary (layer) pytree
# --------------------------------------------------------------------------

def apply_rule_tree(rule: UpdateRule, params, grads, states, labels, hp,
                    step, shardings=None):
    """Apply ``rule`` leaf-wise with per-group hyperparameters.

    ``states`` has one rule-state per param leaf; ``labels`` is an int
    pytree matching ``params`` (group index per leaf, from ``Opt.labels``);
    ``hp`` is the tuple of resolved per-group hparam dicts from
    ``Opt.resolve`` — labels are static, hparam values may be traced.
    ``shardings`` (on a mesh) holds each leaf's ``NamedSharding``, which
    the rule is told (the caller has constrained the gradient to it).
    """
    treedef = jax.tree.structure(params)
    p_flat = treedef.flatten_up_to(params)
    g_flat = treedef.flatten_up_to(grads)
    s_flat = treedef.flatten_up_to(states)
    l_flat = treedef.flatten_up_to(labels)
    sh_flat = (treedef.flatten_up_to(shardings) if shardings is not None
               else [None] * len(p_flat))
    new_p, new_s = [], []
    with jax.named_scope("update"):
        for p, g, s, lab, sh in zip(p_flat, g_flat, s_flat, l_flat, sh_flat):
            np_, ns_ = rule.update(p, g, s, hp[lab], step, sharding=sh)
            new_p.append(np_)
            new_s.append(ns_)
    return treedef.unflatten(new_p), treedef.unflatten(new_s)


def _constrain(grads, shardings):
    """``grads`` constrained leaf-wise to ``shardings`` (None: as they are)."""
    if shardings is None:
        return grads
    return jax.tree.map(jax.lax.with_sharding_constraint, grads, shardings)


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_zeros_like(t):
    return jax.tree.map(jnp.zeros_like, t)


# --------------------------------------------------------------------------
# Scanned-stack forward/backward with inline updates
# --------------------------------------------------------------------------

class StackResiduals(NamedTuple):
    """What the forward scan saves: one input activation per layer."""

    saved_x: Any          # [L, ...] stacked layer inputs
    x_out: Any            # final activation


def stack_forward(
    body: Callable,
    stacked_params,
    ctx,
    x,
    xs_aux=None,
    *,
    residual_constraint: Optional[Callable[[Any], Any]] = None,
) -> StackResiduals:
    """Forward ``lax.scan`` over a layer stack, saving layer inputs.

    ``body(layer_params, ctx, x, aux) -> x`` is one layer's forward.
    ``ctx`` is a pytree visible to every layer (shared weights, encoder
    output, rope tables...).  ``xs_aux`` optionally supplies per-layer
    non-learned scan inputs (e.g. layer indices).
    ``residual_constraint`` applies a sharding constraint to each saved
    residual (sequence-sharding keeps activation memory on-chip at scale).
    """
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if xs_aux is None:
        xs_aux = jnp.arange(L, dtype=jnp.int32)

    def fwd(carry_x, xs):
        layer_p, aux = xs
        saved = carry_x
        if residual_constraint is not None:
            saved = residual_constraint(saved)
        y = body(layer_p, ctx, carry_x, aux)
        return y, saved

    with jax.named_scope("fwd"):
        x_out, saved_x = jax.lax.scan(fwd, x, (stacked_params, xs_aux))
    return StackResiduals(saved_x=saved_x, x_out=x_out)


def stack_backward_update(
    body: Callable,
    rule: UpdateRule,
    stacked_params,
    stacked_states,
    ctx,
    residuals: StackResiduals,
    dx_out,
    xs_aux=None,
    *,
    labels,
    hp,
    step,
    grad_constraint=None,
):
    """Reverse scan: per-layer VJP + immediate optimizer update.

    Returns ``(dx_in, d_ctx, new_stacked_params, new_stacked_states)``.
    ``d_ctx`` is the accumulated gradient w.r.t. ``ctx`` (shared weights /
    cross-attended activations), summed over layers in the scan carry.

    ``grad_constraint`` (perf, §Perf H2): one layer's parameter
    shardings (``NamedSharding`` per leaf, ``sharding/rules.py``
    ``make_grad_constraint``).  Each layer gradient is constrained to its
    parameter's sharding *before* the update consumes it, which turns the
    full-tensor fp32 all-reduce of dW (the ZeRO-2 sin) into a
    reduce-scatter, and the rule updates each device's shard with only
    O(m+n) cross-shard traffic for the factored-moment row/col sums.
    """
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if xs_aux is None:
        xs_aux = jnp.arange(L, dtype=jnp.int32)

    # fp32 accumulators for ctx grads (shared params are few; activations
    # accumulate in their own dtype to bound memory).
    d_ctx0 = _tree_zeros_like(ctx)

    def bwd(carry, xs):
        dx, d_ctx = carry
        layer_p, layer_s, x_in, aux = xs
        # Per-layer remat: re-run the layer forward under vjp.
        with jax.named_scope("recompute"):
            _, vjp = jax.vjp(lambda p, c, xi: body(p, c, xi, aux),
                             layer_p, ctx, x_in)
        with jax.named_scope("grad"):
            g_layer, g_ctx, dx_in = vjp(dx)
            g_layer = _constrain(g_layer, grad_constraint)
        # >>> the LOMO moment: this layer's grads are consumed *here* <<<
        new_p, new_s = apply_rule_tree(rule, layer_p, g_layer, layer_s,
                                       labels, hp, step, grad_constraint)
        return (dx_in, _tree_add(d_ctx, g_ctx)), (new_p, new_s)

    with jax.named_scope("bwd"):
        (dx_in, d_ctx), (new_params, new_states) = jax.lax.scan(
            bwd, (dx_out, d_ctx0),
            (stacked_params, stacked_states, residuals.saved_x, xs_aux),
            reverse=True)
    return dx_in, d_ctx, new_params, new_states


def stack_grads(
    body: Callable,
    stacked_params,
    ctx,
    residuals: StackResiduals,
    dx_out,
    xs_aux=None,
):
    """Backward scan that only *collects* grads (no update) — used by the
    two-pass global-grad-norm mode and by fused-vs-unfused equivalence tests."""
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if xs_aux is None:
        xs_aux = jnp.arange(L, dtype=jnp.int32)
    d_ctx0 = _tree_zeros_like(ctx)

    def bwd(carry, xs):
        dx, d_ctx = carry
        layer_p, x_in, aux = xs
        with jax.named_scope("recompute"):
            _, vjp = jax.vjp(lambda p, c, xi: body(p, c, xi, aux),
                             layer_p, ctx, x_in)
        with jax.named_scope("grad"):
            g_layer, g_ctx, dx_in = vjp(dx)
        return (dx_in, _tree_add(d_ctx, g_ctx)), g_layer

    with jax.named_scope("bwd"):
        (dx_in, d_ctx), g_stack = jax.lax.scan(
            bwd, (dx_out, d_ctx0),
            (stacked_params, residuals.saved_x, xs_aux), reverse=True)
    return dx_in, d_ctx, g_stack


# --------------------------------------------------------------------------
# Whole-model fused train step for the standard decoder-LM layout.
# Models with extra streams (enc-dec, hybrid) wire the helpers themselves.
# --------------------------------------------------------------------------

class FusedSpec(NamedTuple):
    """Scan structure of a model, as consumed by :func:`fused_train_step`.

    params layout: ``{"outer": pytree, "shared": pytree, "stacks": {name: [L,...]}}``
      * ``outer``  — prologue/epilogue parameters (embeddings, final norm, head)
      * ``shared`` — parameters used by *every* layer (zamba2's shared block);
        grads accumulate across layers, updated once per step
      * ``stacks`` — ordered stacked layer pytrees

    functions:
      * ``prologue(outer, batch) -> x0``
      * ``bodies[name](layer_params, ctx, x, aux) -> x`` with
        ``ctx = (shared, pro_ctx)`` where ``pro_ctx`` is any activation
        context the prologue wants visible to all layers (rope tables, masks)
      * ``epilogue(outer, x, batch) -> (loss, metrics)``
      * ``pro_ctx(outer, batch) -> pytree`` (non-learned context; default ())
    """

    prologue: Callable
    bodies: dict
    epilogue: Callable
    pro_ctx: Callable = lambda outer, batch: ()


def fused_train_step(
    spec: FusedSpec,
    opt: Opt,
    params,
    opt_state: OptState,
    batch,
    *,
    hparams=None,
    residual_constraint=None,
    global_grad_norm: Optional[float] = None,
    grad_constraint=None,
):
    """One fused LOMO/AdaLomo training step.

    ``opt_state`` is the v2 :class:`OptState` from ``opt.init(params)`` —
    the same single layout as the unfused ``Opt.step`` path.  ``hparams``
    is the call-time hyperparameter pytree (``Opt.resolve`` semantics:
    dict of scalars, optional per-group overrides, bare scalar = lr);
    its values may be traced, so lr/β/decay schedules never recompile.
    Returns ``(new_params, new_opt_state, loss, metrics)``.

    When ``global_grad_norm`` is set, runs LOMO's two-pass variant: pass 1
    computes the global gradient norm (grads discarded layer-by-layer), pass 2
    re-runs backward applying the clipped update — reproducing the paper's
    §2.1 "two backward passes" cost for the Appendix-B comparison.

    ``grad_constraint(group)`` (on a mesh) gives the parameter shardings
    of one layer of the stack ``group``, or of the ``"outer"`` and
    ``"shared"`` leaves; the update of each runs on its shards.
    """
    rule = opt.rule
    hp = opt.resolve(hparams)
    labels = opt.labels(params)
    step = opt_state.step + 1
    stepf = step.astype(jnp.float32)
    moments = opt_state.moments
    outer, shared, stacks = params["outer"], params["shared"], params["stacks"]

    # ---- forward ----
    with jax.named_scope("head"):
        x0, pro_vjp = jax.vjp(lambda o: spec.prologue(o, batch), outer)
        ctx_act = spec.pro_ctx(outer, batch)
    residuals: dict[str, StackResiduals] = {}
    x = x0
    for name, stacked in stacks.items():
        res = stack_forward(spec.bodies[name], stacked, (shared, ctx_act), x,
                            residual_constraint=residual_constraint)
        residuals[name] = res
        x = res.x_out
    with jax.named_scope("head"):
        loss, epi_vjp, metrics = jax.vjp(
            lambda o, xx: spec.epilogue(o, xx, batch), outer, x,
            has_aux=True)
        # ---- backward + inline update ----
        g_outer_epi, dx = epi_vjp(jnp.ones_like(loss))

    def _sqsum(tree):
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.float32(0.0)
        return sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)

    if global_grad_norm is not None:
        # LOMO's two-pass mode (paper §2.1): pass 1 walks the entire backward
        # graph just to obtain the global grad norm; grads of each layer are
        # discarded as soon as their squared sum is accumulated.
        sq = jnp.float32(0.0)
        dxn = dx
        d_shared_n = _tree_zeros_like(shared)
        for name in reversed(list(stacks.keys())):
            dxn, (d_sh, _), g_stack = stack_grads(
                spec.bodies[name], stacks[name], (shared, ctx_act),
                residuals[name], dxn)
            d_shared_n = _tree_add(d_shared_n, d_sh)
            sq = sq + _sqsum(g_stack)
        (g_outer_pro_n,) = pro_vjp(dxn)
        sq = sq + _sqsum(_tree_add(g_outer_epi, g_outer_pro_n))
        sq = sq + _sqsum(d_shared_n)
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, global_grad_norm / (gnorm + 1e-6))
        # Fold the clip into every group's lr — hparams stay data.
        hp = tuple({**d, "lr": d["lr"] * scale} for d in hp)

    new_stacks, new_stack_m = {}, {}
    d_shared = _tree_zeros_like(shared)
    for name in reversed(list(stacks.keys())):
        gc = grad_constraint(name) if grad_constraint is not None else None
        dx, (d_sh, _), new_p, new_s = stack_backward_update(
            spec.bodies[name], rule, stacks[name], moments["stacks"][name],
            (shared, ctx_act), residuals[name], dx,
            labels=labels["stacks"][name], hp=hp, step=stepf,
            grad_constraint=gc)
        new_stacks[name] = new_p
        new_stack_m[name] = new_s
        d_shared = _tree_add(d_shared, d_sh)

    with jax.named_scope("head"):
        (g_outer_pro,) = pro_vjp(dx)
        g_outer = _tree_add(g_outer_epi, g_outer_pro)
        sh_outer, sh_shared = ((grad_constraint("outer"),
                                grad_constraint("shared"))
                               if grad_constraint is not None else (None, None))
        new_outer, new_outer_m = apply_rule_tree(
            rule, outer, _constrain(g_outer, sh_outer), moments["outer"],
            labels["outer"], hp, stepf, sh_outer)
        new_shared, new_shared_m = apply_rule_tree(
            rule, shared, _constrain(d_shared, sh_shared), moments["shared"],
            labels["shared"], hp, stepf, sh_shared)

    new_params = {"outer": new_outer, "shared": new_shared,
                  "stacks": new_stacks}
    new_opt = OptState(
        step=step,
        moments={"outer": new_outer_m, "shared": new_shared_m,
                 "stacks": new_stack_m})
    return new_params, new_opt, loss, metrics


def unfused_loss_fn(spec: FusedSpec, params, batch):
    """The same model as one differentiable function — for jax.grad-based
    baselines (AdamW/Adafactor) and fused-vs-unfused equivalence tests."""
    outer, shared, stacks = params["outer"], params["shared"], params["stacks"]
    x = spec.prologue(outer, batch)
    ctx_act = spec.pro_ctx(outer, batch)
    for name, stacked in stacks.items():
        body = spec.bodies[name]

        def fwd(carry_x, xs):
            layer_p, aux = xs
            return body(layer_p, (shared, ctx_act), carry_x, aux), None

        L = jax.tree.leaves(stacked)[0].shape[0]
        x, _ = jax.lax.scan(fwd, x, (stacked, jnp.arange(L, dtype=jnp.int32)))
    loss, metrics = spec.epilogue(outer, x, batch)
    return loss, metrics
