"""The hook pipeline: checkpoint, eval, logging, fault and history capture
as ordered callbacks on a five-event protocol.

Events (dispatched in hook-list order by ``repro.run.runner.run``):

  ``on_run_start(ctx)``                 once, after init/restore, before
                                        the first step;
  ``on_step_end(ctx, ev)``              after every completed step, with a
                                        :class:`StepEvent`;
  ``on_eval(ctx, step, metrics)``       whenever an evaluation ran
                                        (emitted by :class:`EvalHook` via
                                        ``ctx.dispatch_eval`` — every hook
                                        sees it, so history capture and
                                        logging don't special-case eval);
  ``on_recover(ctx, restored_step)``    fault recovery rewound the run to
                                        ``restored_step``: hooks that
                                        accumulate per-step state must
                                        discard entries at/after it, or
                                        they double-count the re-executed
                                        steps;
  ``on_exit(ctx)``                      once, after the last step (also on
                                        the exception path), for draining
                                        async work.

Hooks are host-side only: they read ``ctx.params/opt_state`` and device
scalars but never feed anything back into the jitted step, which is why
the pipeline adds **zero steady-state recompiles** (asserted in
``tests/run/test_hooks.py``).  The default pipeline order (straggler →
heartbeat → history → logging → metrics → eval → checkpoint) puts
measurement before side effects: a checkpoint at step N always contains
exactly the state whose metrics step N's hooks observed.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from pathlib import Path
from typing import Any, Callable, Optional

from repro.telemetry.schema import header_record, jsonify
from repro.train.fault import Heartbeat, StragglerMonitor


@dataclasses.dataclass
class StepEvent:
    """What ``on_step_end`` sees: the 0-based step index, **host** scalars
    (loss, metrics dict, hparams pytree — the runner performs ONE bundled
    ``jax.device_get`` per step and converts scalar leaves to Python
    floats before dispatch), and the host wall-clock seconds from the
    previous step's results reaching the host to this step's (sync to
    sync, so a slow device step shows in its own ``dt``).  Hooks must
    never sync on a device value themselves — that is repro-lint rule
    R2."""

    step: int
    loss: Any
    metrics: Any
    hparams: dict
    dt: float


class Hook:
    """Base class: every event defaults to a no-op, so hooks implement
    only what they observe."""

    def on_run_start(self, ctx) -> None:
        pass

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        pass

    def on_eval(self, ctx, step: int, metrics: dict) -> None:
        pass

    def on_recover(self, ctx, restored_step: int) -> None:
        """Fault recovery rewound the run to ``restored_step``; hooks that
        accumulate per-step state discard everything at or after it so the
        final record matches an uninterrupted run."""
        pass

    def on_exit(self, ctx) -> None:
        pass


class HistoryHook(Hook):
    """Captures the training curve — the benchmarks' history dict
    (kept key-compatible with the old ``Trainer.fit`` output)."""

    def __init__(self):
        self.history = {"step": [], "loss": [], "accuracy": [], "lr": [],
                        "eval_loss": [], "eval_step": []}

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        self.history["step"].append(ev.step)
        self.history["loss"].append(ev.loss)
        self.history["accuracy"].append(ev.metrics["accuracy"])
        self.history["lr"].append(ev.hparams["lr"])

    def on_eval(self, ctx, step: int, metrics: dict) -> None:
        self.history["eval_loss"].append(metrics["loss"])
        self.history["eval_step"].append(step)

    def on_recover(self, ctx, restored_step: int) -> None:
        h = self.history
        keep = sum(1 for s in h["step"] if s < restored_step)
        for k in ("step", "loss", "accuracy", "lr"):
            del h[k][keep:]
        keep_ev = sum(1 for s in h["eval_step"] if s < restored_step)
        for k in ("eval_loss", "eval_step"):
            del h[k][keep_ev:]


class LoggingHook(Hook):
    def __init__(self, every: int, log_fn: Callable[[str], None] = print,
                 total: Optional[int] = None):
        self.every = every
        self.log = log_fn
        self.total = total

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        last = self.total is not None and ev.step == self.total - 1
        if self.every and (ev.step % self.every == 0 or last):
            self.log(f"step {ev.step:5d} loss {ev.loss:.4f} "
                     f"acc {ev.metrics['accuracy']:.3f} "
                     f"lr {ev.hparams['lr']:.2e} "
                     f"({ev.dt*1e3:.0f} ms)")

    def on_eval(self, ctx, step: int, metrics: dict) -> None:
        self.log(f"  eval loss {metrics['loss']:.4f} "
                 f"ppl {metrics['ppl']:.2f} acc {metrics['accuracy']:.3f}")


class MetricsHook(Hook):
    """JSONL metrics exporter: one record per observed step — step, loss,
    lr, wall dt, real-token throughput (tokens/s from the step's masked-CE
    ``ntokens`` metric) and padding efficiency (real tokens / slot
    tokens).  Under segment packing the efficiency column is the padding
    tax the packer recovered; for padded ragged batches it shows what is
    being lost.  Honors the rewind contract like :class:`HistoryHook`:
    ``on_recover`` drops records at/after the restored step and rewrites
    the file, so the JSONL always reads as the uninterrupted run's
    record.  The same contract extends across *process* restarts: a
    resumed run (``ctx.start_step > 0``) fast-forwards by keeping the
    existing records before the restored step and truncating the
    re-executed tail, so one metrics file carries the whole fleet-level
    history of a preempted-and-resumed run.

    Besides per-step records, the stream carries *event* records
    (``{"event": kind, "step": N, ...}``) from the liveness hooks —
    heartbeat stalls and straggler steps annotate themselves here via
    :meth:`annotate` (thread-safe; the heartbeat watchdog fires from its
    own thread), so one JSONL file is the single record of throughput
    *and* liveness.

    Since Telemetry v1 the file is a schema-versioned stream
    (``repro.telemetry.schema``): it opens with a ``{"schema": 1,
    "stream": "train"}`` header, and when the run's
    :class:`~repro.telemetry.probes.ObservabilitySpec` is enabled the
    optimizer-health scalars arriving in ``ev.metrics["opt_health"]``
    (already host values — they rode the runner's one bundled transfer)
    are recorded as ``probe`` records at the spec's cadence.  Headers
    are never stored in ``records`` — the rewind/fast-forward contract
    stays step-keyed over data records only — and legacy headerless
    files still resume cleanly."""

    def __init__(self, path, every: int = 1):
        self.path = str(path)
        self.every = max(1, int(every))
        self.records: list = []
        self._slot_tokens: Optional[int] = None
        self._fh = None
        self._lock = threading.Lock()

    def _rewrite(self) -> None:
        if self._fh is not None:
            self._fh.close()
        self._fh = open(self.path, "w")
        self._fh.write(json.dumps(header_record("train")) + "\n")
        for r in self.records:
            self._fh.write(json.dumps(r) + "\n")
        self._fh.flush()

    def on_run_start(self, ctx) -> None:
        d = ctx.spec.data
        if d is not None:
            self._slot_tokens = d.global_batch * d.seq_len
        p = Path(self.path)
        parent = p.parent
        if str(parent) not in ("", "."):
            parent.mkdir(parents=True, exist_ok=True)
        with self._lock:
            self.records = []
            if ctx.start_step > 0 and p.exists():
                # cross-process resume: keep the pre-restore record,
                # truncate the tail the resumed run re-executes
                for line in p.read_text().splitlines():
                    try:
                        r = json.loads(line)
                    except ValueError:  # crash-truncated last line
                        continue
                    if "schema" in r:
                        continue   # header: re-emitted by _rewrite
                    if r.get("step", ctx.start_step) < ctx.start_step:
                        self.records.append(r)
            self._rewrite()

    def _append(self, rec: dict) -> None:
        with self._lock:
            self.records.append(rec)
            if self._fh is not None:
                self._fh.write(json.dumps(rec) + "\n")
                self._fh.flush()

    def annotate(self, kind: str, step: int, **payload) -> None:
        """Append an event record (liveness signals: heartbeat stalls,
        straggler steps, preemption) to the JSONL stream."""
        self._append({"event": kind, "step": int(step), **payload})

    def record_anomaly(self, step: int, reason: str, **payload) -> None:
        """Append an ``anomaly`` record (training-sentinel verdicts:
        schema kind ``anomaly``, marker = the detection reason).  Rides
        the same rewind contract as every step-keyed record: a rollback's
        own record is written *after* on_recover truncation, stamped with
        the restored step, so it survives in the merged stream."""
        self._append(jsonify(
            {"anomaly": reason, "step": int(step), **payload}))

    def _record_probes(self, ctx, step: int, health) -> None:
        """Record the step's optimizer-health pytree (already host-side)
        as probe records at the ObservabilitySpec cadence.  The device
        computes the probes every step; *recording* is what's cadenced —
        that split is what keeps the jit cache at one entry."""
        ospec = getattr(ctx.spec, "observe", None)
        if ospec is None or not ospec.enabled:
            return
        if step % ospec.optimizer_every == 0:
            self._append(jsonify(
                {"probe": "opt_health", "step": step,
                 "group_ratio": health.get("group_ratio", {}),
                 "eff_lr": health.get("eff_lr", {})}))
        factored = health.get("factored")
        if factored and step % ospec.resolved_factored_every() == 0:
            self._append(jsonify(
                {"probe": "factored", "step": step, **factored}))

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        health = (ev.metrics.get("opt_health")
                  if isinstance(ev.metrics, dict) else None)
        if health is not None:
            self._record_probes(ctx, ev.step, health)
        if ev.step % self.every:
            return
        ntok = ev.metrics.get("ntokens", 0.0)
        rec = {"step": ev.step, "loss": ev.loss,
               "lr": ev.hparams["lr"], "dt_s": ev.dt,
               "ntokens": ntok,
               "tokens_per_s": (ntok / ev.dt) if ev.dt > 0 else 0.0}
        if self._slot_tokens:
            rec["padding_efficiency"] = ntok / self._slot_tokens
        self._append(rec)

    def on_recover(self, ctx, restored_step: int) -> None:
        # Step-keyed records rewind (the replay re-emits them); ``event``
        # records are the host-side incident log (recover, preempt,
        # heartbeat stalls) — replay never re-emits those, so truncating
        # them would erase real faults from the audit trail.
        with self._lock:
            self.records = [r for r in self.records
                            if "event" in r
                            or r.get("step", restored_step) < restored_step]
            self._rewrite()

    def on_exit(self, ctx) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


def find_metrics_hook(hooks) -> Optional["MetricsHook"]:
    """The pipeline's MetricsHook, if any (liveness hooks route their
    signals into its JSONL stream)."""
    for h in hooks:
        if isinstance(h, MetricsHook):
            return h
    return None


class EvalHook(Hook):
    """Runs held-out eval every ``every`` steps and broadcasts the result
    to the whole pipeline via ``ctx.dispatch_eval``.

    Two stream modes: a plain ``eval_iter`` (caller-owned; cannot be
    rewound across resume/recovery), or an ``iter_factory(start_batch)``
    — the default pipeline's mode — which makes the eval stream a pure
    function of how many evals the run has completed, so a resumed or
    fault-recovered run consumes exactly the batches the uninterrupted
    run would have."""

    def __init__(self, eval_iter=None, every: int = 0, n_batches: int = 4,
                 *, iter_factory=None):
        assert (eval_iter is None) != (iter_factory is None), \
            "pass exactly one of eval_iter / iter_factory"
        self.eval_iter = eval_iter
        self.iter_factory = iter_factory
        self.every = every
        self.n_batches = n_batches

    def _rewind(self, step: int) -> None:
        if self.iter_factory is None or not self.every:
            return
        consumed = (step // self.every) * self.n_batches
        self.eval_iter = self.iter_factory(consumed)

    def on_run_start(self, ctx) -> None:
        self._rewind(ctx.start_step)

    def on_recover(self, ctx, restored_step: int) -> None:
        self._rewind(restored_step)

    def evaluate(self, ctx) -> dict:
        import jax
        import jax.numpy as jnp
        loss_fn = ctx.program.loss_fn
        tot, acc = 0.0, 0.0
        for _ in range(self.n_batches):
            batch = jax.tree.map(jnp.asarray, next(self.eval_iter))
            loss, metrics = loss_fn(ctx.params, batch)
            tot += float(loss)
            acc += float(metrics["accuracy"])
        tot /= self.n_batches
        return {"loss": tot, "ppl": float(jnp.exp(tot)),
                "accuracy": acc / self.n_batches}

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        if self.every and (ev.step + 1) % self.every == 0:
            ctx.dispatch_eval(ev.step, self.evaluate(ctx))


class CheckpointHook(Hook):
    """Async checkpoint save every ``every`` steps; drains on exit.  The
    saved tree is ``(params, opt_state)`` with the data step recorded so
    resume is exactly deterministic."""

    def __init__(self, manager, every: int):
        self.manager = manager
        self.every = every

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        if self.every and (ev.step + 1) % self.every == 0:
            extra = {"data_step": ev.step + 1}
            if getattr(ctx, "sentinel", None) is not None:
                # monitor counters + device-state snapshot: a resumed run
                # rebuilds the sentinel's cross-step memory bitwise
                extra["sentinel"] = ctx.sentinel.to_extra()
            self.manager.save(ev.step + 1, (ctx.params, ctx.opt_state),
                              extra=extra)

    def on_exit(self, ctx) -> None:
        self.manager.wait()


class HeartbeatHook(Hook):
    """Watchdog: marks the run wedged if steps stop completing.  A stall
    is annotated into the MetricsHook JSONL stream (``{"event":
    "heartbeat_stall", ...}``) when the pipeline has one, so the metrics
    file carries liveness alongside throughput."""

    def __init__(self, timeout_s: float,
                 on_stall: Optional[Callable[[], None]] = None):
        self.timeout_s = timeout_s
        self._on_stall = on_stall
        self.heartbeat: Optional[Heartbeat] = None
        self._last_step = 0

    def on_run_start(self, ctx) -> None:
        self._last_step = ctx.start_step
        metrics = find_metrics_hook(ctx.hooks)

        def fire():
            # annotate runs from the watchdog thread — MetricsHook locks
            if metrics is not None:
                metrics.annotate("heartbeat_stall", self._last_step,
                                 timeout_s=self.timeout_s)
            if self._on_stall is not None:
                self._on_stall()
            else:
                ctx.log("HEARTBEAT STALL")

        self.heartbeat = Heartbeat(self.timeout_s, on_stall=fire)
        self.heartbeat.start()

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        self._last_step = ev.step
        if self.heartbeat is not None:
            self.heartbeat.beat()

    def on_exit(self, ctx) -> None:
        if self.heartbeat is not None:
            self.heartbeat.stop()


class StragglerHook(Hook):
    """Feeds per-step wall time into a :class:`StragglerMonitor` (EMA
    outlier detection; the coordinator's evict signal at scale).
    Flagged steps are annotated into the MetricsHook JSONL stream
    (``{"event": "straggler", ...}``) when the pipeline has one."""

    def __init__(self, monitor: Optional[StragglerMonitor] = None):
        self.monitor = monitor if monitor is not None else StragglerMonitor()

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        if self.monitor.observe(ev.step, ev.dt):
            metrics = find_metrics_hook(ctx.hooks)
            if metrics is not None:
                _, dt, ema = self.monitor.events[-1]
                metrics.annotate("straggler", ev.step, dt_s=dt, ema_s=ema)


class TimingHook(Hook):
    """Wall-clock accounting: total run seconds and mean us/step."""

    def __init__(self):
        self.t0 = None
        self.wall_s = 0.0
        self.n_steps = 0

    def on_run_start(self, ctx) -> None:
        self.t0 = time.time()

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        self.n_steps += 1

    def on_exit(self, ctx) -> None:
        if self.t0 is not None:
            self.wall_s = time.time() - self.t0

    @property
    def us_per_step(self) -> float:
        return self.wall_s / max(self.n_steps, 1) * 1e6


class ProfilerHook(Hook):
    """jax profiler trace for a configurable step window.

    Traces steps ``[start, start + steps)`` (0-based) into ``dir`` and
    stamps the artifact with the originating RunSpec
    (``<dir>/profile.runspec.json`` sidecar, the dryrun-artifact idiom) so
    a trace is always attributable to the exact spec that produced it.
    The default window skips step 0, which is dominated by compilation.

    Resume/recovery contract: a run restored *past* the window does not
    re-trace (the artifact belongs to the steps that already executed);
    a fault recovery while tracing stops the trace and keeps what was
    captured.  ``on_exit`` stops a still-active trace on any exit path,
    so a preempted run leaves a readable artifact."""

    def __init__(self, dir, start: int = 1, steps: int = 2):
        self.dir = str(dir)
        self.start = int(start)
        self.steps = int(steps)
        self.active = False
        self.done = False

    def _begin(self, ctx) -> None:
        # a requested trace that cannot start fails the run: a silently
        # missing trace would be read as a measurement that was made
        import jax.profiler
        jax.profiler.start_trace(self.dir)
        self.active = True

    def _end(self, ctx) -> None:
        if not self.active:
            return
        self.active = False
        self.done = True
        import jax.profiler
        jax.profiler.stop_trace()

    def on_run_start(self, ctx) -> None:
        out = Path(self.dir)
        out.mkdir(parents=True, exist_ok=True)
        (out / "profile.runspec.json").write_text(ctx.spec.to_json(indent=1))
        if ctx.start_step > self.start:
            self.done = True       # window already executed pre-resume
        elif ctx.start_step == self.start:
            self._begin(ctx)

    def on_step_end(self, ctx, ev: StepEvent) -> None:
        if self.done:
            return
        if self.active and ev.step + 1 >= self.start + self.steps:
            self._end(ctx)
        elif not self.active and ev.step + 1 == self.start:
            self._begin(ctx)

    def on_recover(self, ctx, restored_step: int) -> None:
        self._end(ctx)

    def on_exit(self, ctx) -> None:
        self._end(ctx)
