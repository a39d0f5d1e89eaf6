"""``run(spec)`` — the one entrypoint for train / dryrun / benchmarks.

Assembles arch + :class:`~repro.run.program.StepProgram` + data + hook
pipeline from a :class:`~repro.run.spec.RunSpec` and drives the loop.
Every knob has a programmatic override (prebuilt program, warm-start
params, injected iterators, extra hooks) so benchmarks and tests compose
scenarios without re-wiring the loop — the spec stays the single source
of truth for what is *declarable*, the overrides carry what is not.

Default hook order (measurement before side effects; see
``repro.run.hooks``): straggler → heartbeat → profiler → history →
logging → metrics → eval → checkpoint → preemption → user hooks.

When ``spec.mesh.shape`` names a concrete device mesh, the loop runs the
*same* step program sharded on it (``repro.fleet.elastic``): checkpoint
restore re-shards onto the mesh, so a run resumes elastically on a
smaller or larger fleet by editing only that field.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional, Sequence, Type

import jax
import jax.numpy as jnp
from jax.errors import JaxRuntimeError

from repro.run import hooks as hooks_lib
from repro.run.data import EVAL_SEED_OFFSET, make_batch_iter
from repro.run.program import StepProgram, build_step_program
from repro.run.spec import RunSpec


def _host_scalars(tree):
    """Convert the per-step observables (already on host via one bundled
    ``jax.device_get``) to plain Python floats; non-scalar leaves pass
    through as numpy arrays."""
    def conv(x):
        if isinstance(x, (bool, int, float)) or x is None:
            return x
        if getattr(x, "ndim", None) == 0:
            return float(x)
        return x
    return jax.tree.map(conv, tree)




@dataclasses.dataclass
class RunContext:
    """What hooks see: the spec, the program, the live (params, opt_state)
    after the most recent step, and the dispatch surface."""

    spec: RunSpec
    program: StepProgram
    params: Any
    opt_state: Any
    log: Callable[[str], None]
    hooks: tuple
    ckpt_manager: Any = None
    start_step: int = 0
    # SentinelMonitor when spec.sentinel.enabled (CheckpointHook persists
    # its to_extra() so resume rebuilds the device SentinelState exactly).
    sentinel: Any = None

    def dispatch_eval(self, step: int, metrics: dict) -> None:
        for h in self.hooks:
            h.on_eval(self, step, metrics)


@dataclasses.dataclass
class RunResult:
    params: Any
    opt_state: Any
    history: dict
    start_step: int
    program: StepProgram
    hooks: tuple

    def find_hook(self, cls: Type) -> Optional[hooks_lib.Hook]:
        for h in self.hooks:
            if isinstance(h, cls):
                return h
        return None


def _default_hooks(spec: RunSpec, *, eval_iter, eval_factory, ckpt_manager,
                   log_fn, user_hooks) -> tuple:
    """The standard pipeline; a user hook of the same class replaces the
    default instance (so e.g. a caller-owned StragglerMonitor keeps
    accumulating across runs)."""
    user = tuple(user_hooks)

    def absent(cls):
        return not any(isinstance(h, cls) for h in user)

    out = []
    if absent(hooks_lib.StragglerHook):
        out.append(hooks_lib.StragglerHook())
    if spec.fault.heartbeat_timeout_s > 0 and absent(hooks_lib.HeartbeatHook):
        out.append(hooks_lib.HeartbeatHook(spec.fault.heartbeat_timeout_s))
    if spec.profile.dir and absent(hooks_lib.ProfilerHook):
        out.append(hooks_lib.ProfilerHook(spec.profile.dir,
                                          start=spec.profile.start,
                                          steps=spec.profile.steps))
    if absent(hooks_lib.HistoryHook):
        out.append(hooks_lib.HistoryHook())
    if spec.log_every and absent(hooks_lib.LoggingHook):
        out.append(hooks_lib.LoggingHook(spec.log_every, log_fn,
                                         total=spec.steps.total))
    if spec.metrics_path and absent(hooks_lib.MetricsHook):
        out.append(hooks_lib.MetricsHook(spec.metrics_path))
    if spec.eval.every and absent(hooks_lib.EvalHook):
        if eval_iter is not None:
            out.append(hooks_lib.EvalHook(eval_iter, spec.eval.every,
                                          spec.eval.n_batches))
        elif eval_factory is not None:
            out.append(hooks_lib.EvalHook(every=spec.eval.every,
                                          n_batches=spec.eval.n_batches,
                                          iter_factory=eval_factory))
    if (ckpt_manager is not None and spec.checkpoint.every
            and absent(hooks_lib.CheckpointHook)):
        out.append(hooks_lib.CheckpointHook(ckpt_manager,
                                            spec.checkpoint.every))
    if spec.fault.preempt and ckpt_manager is not None:
        # after CheckpointHook: a preemption boundary that coincides with
        # a scheduled save reuses it.  Lazy import — the fleet layer
        # builds on repro.run, not the other way around.
        from repro.fleet.preempt import PreemptionHook
        if absent(PreemptionHook):
            out.append(PreemptionHook(ckpt_manager))
    return tuple(out) + user


def run(spec: RunSpec, *, arch=None, program: Optional[StepProgram] = None,
        hooks: Sequence[hooks_lib.Hook] = (), params=None, opt_state=None,
        batch_iter: Optional[Iterator[dict]] = None, eval_iter=None,
        ckpt_manager=None, start_step: int = 0, groups=None,
        inject=None, log_fn: Callable[[str], None] = print) -> RunResult:
    """Drive one run end-to-end.  Overrides (all optional):

    ``arch``       an Arch instance for ad-hoc configs (else registry);
    ``program``    a prebuilt StepProgram (else ``build_step_program``);
    ``params`` / ``opt_state``  warm starts (opt_state defaults to a fresh
                   ``opt.init(params)``);
    ``batch_iter`` / ``eval_iter``  injected data streams (else built from
                   ``spec.data``, eval stream seed-offset);
    ``ckpt_manager``  a CheckpointManager (else built from
                   ``spec.checkpoint.dir``); resume restores the latest
                   complete step and fast-forwards the data stream;
    ``hooks``      appended after the default pipeline (same-class user
                   hooks replace the default instance);
    ``start_step`` begin mid-schedule without a checkpoint;
    ``inject``     an in-graph fault :class:`~repro.sentinel.inject.
                   Injection` (chaos harness; requires
                   ``spec.sentinel.enabled`` and no prebuilt program).
    """
    if program is None:
        if spec.mesh.shape is not None:
            # Elastic path: same spec, sharded step.  run_elastic builds
            # the sharded program and re-enters run() with it, so this
            # cannot recurse.
            from repro.fleet.elastic import run_elastic
            return run_elastic(spec, arch=arch, hooks=hooks, params=params,
                               opt_state=opt_state, batch_iter=batch_iter,
                               eval_iter=eval_iter, ckpt_manager=ckpt_manager,
                               start_step=start_step, groups=groups,
                               inject=inject, log_fn=log_fn)
        program = build_step_program(spec, arch, groups=groups,
                                     inject=inject)
    elif inject is not None:
        raise ValueError("inject requires run() to build the program "
                         "(pass inject to build_step_program instead)")
    arch = program.arch

    # --- training sentinel (host side) --------------------------------
    monitor = None
    sent = program.init_sentinel()
    if program.sentinel_enabled:
        from repro.sentinel.policy import SentinelMonitor
        monitor = SentinelMonitor(spec.sentinel)

    if params is None:
        params, opt_state = program.init(spec.seed)
    elif opt_state is None:
        opt_state = program.opt.init(params)

    if spec.mesh.kind != "none" and spec.mesh.shape is None:
        # A sharding *mode* without a concrete shape is only consumed by
        # dry-run lowering.  Say so rather than silently dropping a
        # declared mode on spec replay (set mesh.shape for elastic
        # execution inside run()).
        log_fn(f"note: spec.mesh.kind={spec.mesh.kind!r} is recorded but "
               "run() executes single-process; use launch/dryrun.py for "
               "mesh lowering or set mesh.shape for elastic execution")

    ck = spec.checkpoint
    if ckpt_manager is None and ck.dir:
        from repro.checkpoint.manager import CheckpointManager
        ckpt_manager = CheckpointManager(ck.dir, keep_last=ck.keep_last,
                                         gc_incomplete=ck.gc_incomplete)
    def _restore_sentinel(extra):
        """Rebuild monitor + device SentinelState from checkpoint extra —
        bitwise resume includes the sentinel's cross-step memory."""
        nonlocal sent
        snap = (extra or {}).get("sentinel")
        if monitor is None or not snap:
            return
        from repro.sentinel.guard import state_from_snapshot
        monitor.load_extra(snap)
        if snap.get("state"):
            sent = state_from_snapshot(snap["state"])

    if (ckpt_manager is not None and ck.resume
            and ckpt_manager.latest_step() is not None):
        start_step, (params, opt_state), _extra = ckpt_manager.restore(
            template=(params, opt_state))
        _restore_sentinel(_extra)
        log_fn(f"resumed from step {start_step}")

    def _train_iter(s):
        """The step-keyed train stream from step ``s`` — with quarantined
        ranges substituted when the sentinel has rolled back."""
        if monitor is not None:
            from repro.sentinel.policy import quarantined_batch_iter
            return quarantined_batch_iter(spec, arch, s, monitor)
        return make_batch_iter(spec, arch, s)

    own_batch_iter = batch_iter is None
    if batch_iter is None:
        batch_iter = _train_iter(start_step)
    eval_factory = None
    if eval_iter is None and spec.eval.every and spec.data is not None:
        # The default held-out stream is a pure function of how many eval
        # batches the run has consumed, so EvalHook can fast-forward on
        # resume and rewind on fault recovery (deterministic eval curve).
        def eval_factory(start_batch, _spec=spec, _arch=arch):
            return make_batch_iter(_spec, _arch, start_batch,
                                   seed_offset=EVAL_SEED_OFFSET)

    pipeline = _default_hooks(spec, eval_iter=eval_iter,
                              eval_factory=eval_factory,
                              ckpt_manager=ckpt_manager, log_fn=log_fn,
                              user_hooks=hooks)
    ctx = RunContext(spec=spec, program=program, params=params,
                     opt_state=opt_state, log=log_fn, hooks=pipeline,
                     ckpt_manager=ckpt_manager, start_step=start_step,
                     sentinel=monitor)

    # Transient-failure policy: the jitted step donates (params, opt_state),
    # so a failed call may have consumed its input buffers — re-invoking
    # with the same arguments can never succeed (the flaw in the old
    # Trainer's blind retry).  Recovery therefore goes through the
    # checkpoint: restore the latest complete step, rewind the (stateless,
    # step-keyed) data stream, and resume the loop from there.  Without a
    # checkpoint — or with a caller-injected batch iterator we cannot
    # rewind — the error propagates immediately.  Hooks re-observe the
    # re-executed steps, so the history is the truthful training record.
    failures = 0
    try:
        # on_run_start inside the try: if a hook raises here, earlier
        # hooks that already started (watchdog threads, async writers)
        # still get their on_exit.
        for h in pipeline:
            h.on_run_start(ctx)
        t_last = time.time()
        step = start_step
        while step < spec.steps.total:
            batch = jax.tree.map(jnp.asarray, next(batch_iter))
            hp = program.hparams_fn(step + 1)
            try:
                if sent is None:
                    ctx.params, ctx.opt_state, loss, metrics = program.step(
                        ctx.params, ctx.opt_state, batch, hp)
                else:
                    (ctx.params, ctx.opt_state, loss, metrics,
                     sent) = program.step(ctx.params, ctx.opt_state, batch,
                                          hp, sent)
            except JaxRuntimeError as e:
                failures += 1
                if ckpt_manager is not None:
                    ckpt_manager.wait()  # drain any in-flight async save
                # Every stream must rewind for recovery to reproduce the
                # uninterrupted run: caller-injected train or eval
                # iterators cannot, so the error propagates instead of
                # silently diverging the curves.
                rewindable_eval = all(
                    h.iter_factory is not None for h in pipeline
                    if isinstance(h, hooks_lib.EvalHook) and h.every)
                recoverable = (failures <= spec.fault.retries
                               and own_batch_iter and rewindable_eval
                               and ckpt_manager is not None
                               and ckpt_manager.latest_step() is not None)
                if not recoverable:
                    raise
                # Deterministic (jitterless) exponential backoff before
                # the restore: attempt n waits base * 2^(n-1), capped.
                delay = 0.0
                if spec.fault.retry_backoff_s > 0:
                    delay = min(
                        spec.fault.retry_backoff_s * 2.0 ** (failures - 1),
                        spec.fault.retry_backoff_max_s)
                    time.sleep(delay)
                restored, (p, s), _extra = ckpt_manager.restore(
                    template=(ctx.params, ctx.opt_state))
                _restore_sentinel(_extra)
                log_fn(f"step {step} failed ({type(e).__name__}); "
                       f"restored step {restored} "
                       f"(attempt {failures}/{spec.fault.retries})")
                ctx.params, ctx.opt_state = p, s
                failed_at, step = step, restored
                batch_iter = _train_iter(restored)
                for h in pipeline:
                    h.on_recover(ctx, restored)
                # after on_recover: the truncation must not eat the event
                mh = hooks_lib.find_metrics_hook(pipeline)
                if mh is not None:
                    mh.annotate("recover", restored, attempt=failures,
                                failed_step=failed_at, backoff_s=delay)
                t_last = time.time()
                continue
            now = time.time()
            # The ONE device->host sync of the step loop: hooks receive
            # plain host scalars (the StepEvent contract) so none of them
            # ever blocks on a device value again (repro-lint R2).
            loss_h, metrics_h, hp_h = _host_scalars(
                jax.device_get((loss, metrics, hp)))
            ev = hooks_lib.StepEvent(step=step, loss=loss_h,
                                     metrics=metrics_h,
                                     hparams=hp_h, dt=now - t_last)
            t_last = now
            # The monitor ingests the verdict BEFORE hook dispatch so a
            # boundary checkpoint persists the current device-state
            # snapshot; policy *actions* run after the hooks have seen
            # the step (records first, then recovery).
            anomalous = False
            if monitor is not None:
                verdict = ev.metrics.get("sentinel", {})
                anomalous = monitor.observe(step, verdict)
            for h in pipeline:
                h.on_step_end(ctx, ev)
            if anomalous:
                spc = spec.sentinel
                reason = monitor.classify(verdict)
                mh = hooks_lib.find_metrics_hook(pipeline)
                rewindable_eval = all(
                    h.iter_factory is not None for h in pipeline
                    if isinstance(h, hooks_lib.EvalHook) and h.every)
                rollback = (monitor.wants_rollback() and own_batch_iter
                            and rewindable_eval and ckpt_manager is not None
                            and ckpt_manager.latest_step() is not None)
                action = ("rollback" if rollback else
                          "backoff" if "backoff" in spc.ladder else "skip")
                log_fn(f"sentinel: anomaly at step {step} ({reason}) -> "
                       f"{action} [{monitor.anomalies}/{spc.budget}]")
                if monitor.exhausted():
                    # Loudly, and NOT via a retriable error: a run that
                    # keeps tripping the guard must not silently spin
                    # through restore cycles.
                    from repro.sentinel.policy import AnomalyBudgetExceeded
                    if mh is not None:
                        mh.record_anomaly(step, reason, action="abort",
                                          count=monitor.anomalies)
                    raise AnomalyBudgetExceeded(
                        f"anomaly budget exhausted: {monitor.anomalies} "
                        f"anomalies > budget {spc.budget} "
                        f"(last: {reason} at step {step})")
                if rollback:
                    ckpt_manager.wait()
                    restored, (p, s), _ = ckpt_manager.restore(
                        template=(ctx.params, ctx.opt_state))
                    ctx.params, ctx.opt_state = p, s
                    monitor.quarantine(restored, step + 1)
                    # The device SentinelState deliberately carries
                    # forward: the guard's memory (EMA, seen-clock)
                    # survives the rewind, which also keeps seen-keyed
                    # injected faults from re-firing on replay.
                    batch_iter = _train_iter(restored)
                    for h in pipeline:
                        h.on_recover(ctx, restored)
                    if mh is not None:
                        mh.record_anomaly(restored, reason,
                                          action="rollback",
                                          anomaly_step=step,
                                          quarantine=[restored, step + 1],
                                          count=monitor.anomalies)
                    log_fn(f"sentinel: rolled back to step {restored}; "
                           f"quarantined steps [{restored}, {step + 1})")
                    step = restored
                    t_last = time.time()
                    continue
                if mh is not None:
                    mh.record_anomaly(
                        step, reason, action=action,
                        count=monitor.anomalies,
                        update_norm=verdict.get("update_norm"),
                        ema_ref=verdict.get("ema_ref"))
            step += 1
    finally:
        for h in pipeline:
            h.on_exit(ctx)

    hist = None
    for h in pipeline:
        if isinstance(h, hooks_lib.HistoryHook):
            hist = h.history
            break
    return RunResult(params=ctx.params, opt_state=ctx.opt_state,
                     history=hist if hist is not None else {},
                     start_step=start_step, program=program, hooks=pipeline)
