"""Elastic restore: resume the same RunSpec on a different device mesh.

Checkpoints are mesh-independent (full logical arrays; see
``checkpoint/manager.py``), so "we lost a pod" is a spec edit, not a
migration: change ``spec.mesh.shape`` and resume.  This module owns the
three pieces that make that real:

  * :func:`mesh_from_spec` — rebuild a concrete ``jax.sharding.Mesh``
    from the declarative ``MeshSpec.shape`` (a *subset* of the visible
    devices, so shrinking below the device count is legal — exactly the
    lost-pod case);
  * :func:`program_shardings` — derive (params, opt_state, batch)
    NamedShardings for the program's abstract signature from the
    partition rules in ``sharding/rules.py``.  AdaLomo's factored (r, c)
    second-moment vectors land on the devices that own the rows/columns
    they describe (``opt_pspecs``) — the regime of
    Anil et al., *Memory-Efficient Adaptive Optimization*;
  * :func:`run_elastic` — re-jit the *same* ``StepProgram.fn`` under
    those shardings and drive it through the stock ``run()`` loop with a
    checkpoint manager that restores straight onto the new mesh
    (``restore(shardings=...)``), keeping every fleet property (resume,
    preemption, fault recovery, hooks) identical to the single-process
    path.

Numerics contract (tests/fleet/test_elastic.py): resuming on the *same*
mesh is bitwise; resuming on a *different* mesh matches to tight
tolerance (cross-device reduction order is the only difference).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.run.program import StepProgram, build_step_program
from repro.run.spec import MeshSpec, RunSpec
from repro.sharding import rules as R

_AXES_BY_NDIM = {1: ("data",), 2: ("data", "model"),
                 3: ("pod", "data", "model")}


def mesh_from_spec(mesh: MeshSpec) -> Mesh:
    """Build the concrete mesh ``mesh.shape`` names, from a prefix of the
    visible devices (a sub-mesh, so elastic shrink works on a partially
    lost fleet)."""
    if mesh.shape is None:
        raise ValueError("MeshSpec.shape is required for an elastic mesh")
    need = mesh.n_devices()
    devices = jax.devices()
    if need > len(devices):
        raise ValueError(
            f"mesh shape {mesh.shape} needs {need} devices, only "
            f"{len(devices)} visible (start with --virtual-devices "
            f"{need} on CPU, or shrink spec.mesh.shape)")
    devs = np.array(devices[:need]).reshape(mesh.shape)
    return Mesh(devs, _AXES_BY_NDIM[len(mesh.shape)])


def program_shardings(program: StepProgram, mesh: Mesh):
    """(params, opt_state, batch, hparams[, sentinel]) NamedShardings for
    the program's abstract signature on ``mesh`` — derived from the
    partition rules, so the elastic step is sharded exactly like the
    production pjit path.  The sentinel slot (five 0-d scalars,
    replicated) appears only when the program carries the guard, matching
    ``abstract_args``."""
    axes = R.MeshAxes(mesh)
    args = program.abstract_args()
    params_sds, opt_sds, batch_sds, hp_sds = args[:4]
    p_specs = R.param_pspecs(params_sds, axes)
    o_specs = R.opt_pspecs(opt_sds, params_sds, p_specs, axes)
    b_specs = R.batch_pspecs(batch_sds, axes)
    rep = NamedSharding(mesh, P())
    out = (R.to_shardings(p_specs, mesh),
           R.to_shardings(o_specs, mesh),
           R.to_shardings(b_specs, mesh),
           jax.tree.map(lambda _: rep, hp_sds))
    if len(args) == 5:
        out += (jax.tree.map(lambda _: rep, args[4]),)
    return out


def grad_constraint(spec: RunSpec, arch, mesh: Mesh):
    """The fused step's gradient shardings on ``mesh``
    (``sharding/rules.make_grad_constraint``): each gradient is
    reduce-scattered to its parameter's sharding and updated there, shard
    by shard.  None for the unfused path, which takes no constraints, and
    for the baseline ``MeshSpec(optimized=False)``: its gradients arrive
    all-reduced whole and the update runs whole on every device."""
    if not (spec.mesh.optimized
            and spec.steps.resolved_fused(spec.opt.name)):
        return None
    if arch is None:
        from repro.models.registry import get_arch
        arch = get_arch(spec.model.arch, smoke=spec.model.smoke)
    params = jax.eval_shape(lambda: arch.init_params(jax.random.PRNGKey(0)))
    return R.make_grad_constraint(mesh, R.MeshAxes(mesh), params)


class ElasticCheckpoints:
    """A CheckpointManager view whose ``restore`` defaults to re-sharding
    onto the elastic mesh — the runner's resume and fault-recovery paths
    then place restored state correctly without knowing about meshes."""

    def __init__(self, inner, shardings):
        self._inner = inner
        self._shardings = shardings

    def restore(self, step=None, *, template=None, shardings=None):
        if shardings is None:
            shardings = self._shardings
        return self._inner.restore(step, template=template,
                                   shardings=shardings)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def run_elastic(spec: RunSpec, *, arch=None, hooks=(), params=None,
                opt_state=None, batch_iter=None, eval_iter=None,
                ckpt_manager=None, start_step: int = 0, groups=None,
                inject=None, log_fn=print):
    """``run()`` with the step executed on the ``spec.mesh.shape`` mesh.

    Called by ``run()`` itself whenever the spec names a mesh shape; the
    signature mirrors ``run()``'s overrides.  Builds the program once
    (the fused step with its gradients constrained to the parameters'
    shardings and updated shard by shard), re-jits its pure ``fn`` under
    rule-derived shardings (donated, like the single-process step),
    places initial state on the mesh, and
    hands everything back to the stock loop — resume/recovery restore
    through :class:`ElasticCheckpoints`, landing state on the new mesh.
    """
    mesh = mesh_from_spec(spec.mesh)
    program = build_step_program(spec, arch, groups=groups, inject=inject,
                                 grad_constraint=grad_constraint(spec, arch,
                                                                 mesh))
    shardings = program_shardings(program, mesh)
    p_sh, o_sh, b_sh, hp_sh = shardings[:4]

    # out_shardings pins the donated (params, opt_state) outputs to the
    # *input* shardings: without it GSPMD may propagate a different
    # layout (e.g. a factored [r] vector ending up P('data')) and the
    # next step's in_shardings reject the fed-back state.  loss/metrics
    # are scalars — replicated.
    rep = NamedSharding(mesh, P())
    if len(shardings) == 5:
        # sentinel-guarded 5-arg signature: the SentinelState rides
        # replicated through the same jitted step
        sent_sh = shardings[4]
        sharded_step = jax.jit(
            program.fn,
            in_shardings=(p_sh, o_sh, b_sh, hp_sh, sent_sh),
            out_shardings=(p_sh, o_sh, rep, rep, sent_sh),
            donate_argnums=(0, 1))

        def step(params, opt_state, batch, hp, sent):
            batch = jax.device_put(batch, b_sh)
            with jax.set_mesh(mesh):
                return sharded_step(params, opt_state, batch, hp, sent)
    else:
        sharded_step = jax.jit(program.fn,
                               in_shardings=(p_sh, o_sh, b_sh, hp_sh),
                               out_shardings=(p_sh, o_sh, rep, rep),
                               donate_argnums=(0, 1))

        def step(params, opt_state, batch, hp):
            # commit the host batch to its mesh sharding before dispatch
            # (the runner materializes batches on the default device
            # otherwise); the mesh in context keeps single-device kernels
            # off the mesh (models/layers.py) and runs the AdaLomo kernel
            # whole on every device where no sharding reaches it
            # (core/optimizers.py)
            batch = jax.device_put(batch, b_sh)
            with jax.set_mesh(mesh):
                return sharded_step(params, opt_state, batch, hp)

    def lower(*args):
        with jax.set_mesh(mesh):
            return sharded_step.lower(*args)

    step._cache_size = sharded_step._cache_size  # zero-recompile introspection
    step.lower = lower                           # StepProgram.lower()
    program.step = step

    if params is None:
        params, opt_state = program.init(spec.seed)
    elif opt_state is None:
        opt_state = program.opt.init(params)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)

    ck = spec.checkpoint
    if ckpt_manager is None and ck.dir:
        from repro.checkpoint.manager import CheckpointManager
        ckpt_manager = CheckpointManager(ck.dir, keep_last=ck.keep_last,
                                         gc_incomplete=ck.gc_incomplete)
    if ckpt_manager is not None:
        ckpt_manager = ElasticCheckpoints(ckpt_manager, (p_sh, o_sh))

    log_fn(f"elastic mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
           f"({math.prod(mesh.devices.shape)} of {len(jax.devices())} "
           f"devices)")

    from repro.run.runner import run
    return run(spec, arch=program.arch, program=program, hooks=hooks,
               params=params, opt_state=opt_state, batch_iter=batch_iter,
               eval_iter=eval_iter, ckpt_manager=ckpt_manager,
               start_step=start_step, groups=groups, log_fn=log_fn)
