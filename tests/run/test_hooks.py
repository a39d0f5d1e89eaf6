"""Hook pipeline + run() driver: event protocol, ordering, default
pipeline assembly, checkpoint/resume through the one entrypoint, and the
acceptance guarantee that hooks + schedulable hparams cause **zero
steady-state recompiles** of the jitted step.
"""
import time

import numpy as np
import pytest

from repro.data.pipeline import DataConfig
from repro.run import (CheckpointSpec, CheckpointHook, EvalSpec, FaultSpec,
                       HeartbeatHook, HistoryHook, Hook, LoggingHook,
                       ModelSpec, OptSpec, RunSpec, StepSpec, StragglerHook,
                       build_step_program, run)


def _spec(total=3, **kw):
    base = dict(
        model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
        data=DataConfig(vocab=0, seq_len=32, global_batch=4),
        opt=OptSpec(name="adalomo", lr=1e-3, schedule="constant"),
        steps=StepSpec(total=total),
        log_every=0)
    base.update(kw)
    return RunSpec(**base)


class Recorder(Hook):
    def __init__(self):
        self.events = []

    def on_run_start(self, ctx):
        self.events.append(("run_start", ctx.start_step))

    def on_step_end(self, ctx, ev):
        self.events.append(("step_end", ev.step))

    def on_eval(self, ctx, step, metrics):
        self.events.append(("eval", step))

    def on_exit(self, ctx):
        self.events.append(("exit", None))


# ---------------------------------------------------------------------
# Event protocol
# ---------------------------------------------------------------------

def test_event_sequence_and_payload():
    rec = Recorder()
    res = run(_spec(total=3), hooks=(rec,), log_fn=lambda s: None)
    assert rec.events == [("run_start", 0), ("step_end", 0),
                          ("step_end", 1), ("step_end", 2), ("exit", None)]
    assert res.history["step"] == [0, 1, 2]
    assert len(res.history["loss"]) == 3
    assert np.isfinite(res.history["loss"]).all()
    # constant schedule recorded through the hook
    assert res.history["lr"] == [pytest.approx(1e-3)] * 3


class _SlowResult:
    """A step result whose host read waits: a long device step as the
    runner sees it (the dispatch returns at once, the sync waits)."""

    def __init__(self, value, wait_s):
        self.value, self.wait_s = value, wait_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.wait_s)
        return np.asarray(self.value, dtype=dtype)


def test_step_dt_is_sync_to_sync_and_lands_on_its_step():
    slow, wait = 3, 0.5
    spec = _spec(total=6)
    prog = build_step_program(spec)
    real, calls = prog.step, {"n": 0}

    def step(*a):
        params, opt_state, loss, metrics = real(*a)
        if calls["n"] == slow:
            loss = _SlowResult(loss, wait)
        calls["n"] += 1
        return params, opt_state, loss, metrics

    prog.step = step
    dts = {}

    class Dt(Hook):
        def on_step_end(self, ctx, ev):
            dts[ev.step] = ev.dt

    run(spec, program=prog, hooks=(Dt(),), log_fn=lambda s: None)
    assert dts[slow] >= wait
    assert dts[slow + 1] < wait / 2


def test_eval_event_broadcast_to_all_hooks():
    rec = Recorder()
    res = run(_spec(total=4, eval=EvalSpec(every=2, n_batches=1)),
              hooks=(rec,), log_fn=lambda s: None)
    assert ("eval", 1) in rec.events and ("eval", 3) in rec.events
    assert res.history["eval_step"] == [1, 3]
    assert len(res.history["eval_loss"]) == 2


def test_on_exit_runs_even_when_a_step_raises():
    rec = Recorder()

    def bad_iter():
        yield {"tokens": np.zeros((4, 32), np.int32),
               "labels": np.zeros((4, 32), np.int32)}
        raise RuntimeError("data source died")

    with pytest.raises(RuntimeError, match="data source died"):
        run(_spec(total=3, fault=FaultSpec(retries=0)),
            batch_iter=bad_iter(), hooks=(rec,), log_fn=lambda s: None)
    assert rec.events[-1] == ("exit", None)
    assert ("step_end", 0) in rec.events


def test_on_exit_runs_when_on_run_start_raises():
    rec = Recorder()

    class Bomb(Hook):
        def on_run_start(self, ctx):
            raise RuntimeError("bad hook")

    with pytest.raises(RuntimeError, match="bad hook"):
        run(_spec(total=2), hooks=(rec, Bomb()), log_fn=lambda s: None)
    # rec started before the bomb, and still saw the exit event
    assert rec.events == [("run_start", 0), ("exit", None)]


def _flaky_program(spec, fail_on_call):
    """A StepProgram whose step raises a transient device error on the
    N-th call — after the real (donating) computation already consumed
    its input buffers, like a real late-step failure."""
    from jax.errors import JaxRuntimeError
    from repro.run import build_step_program
    prog = build_step_program(spec)
    real = prog.step
    calls = {"n": 0}

    def step(params, opt_state, batch, hp):
        out = real(params, opt_state, batch, hp)
        calls["n"] += 1
        if calls["n"] == fail_on_call:
            raise JaxRuntimeError("injected ICI flap")
        return out

    prog.step = step
    return prog


def test_transient_failure_recovers_from_checkpoint(tmp_path):
    """A transient device error mid-run restores the latest complete
    checkpoint, rewinds the stateless data stream, and finishes with the
    exact state AND history of an uninterrupted run (donated buffers make
    a blind same-args retry impossible — recovery goes through the
    checkpoint; on_recover truncates re-executed history entries)."""
    # fail on call 6 = step 5, two steps past the step-3 checkpoint, so
    # recovery re-executes steps 3 and 4 — the history-duplication case
    spec = _spec(total=7, eval=EvalSpec(every=2, n_batches=1),
                 checkpoint=CheckpointSpec(dir=str(tmp_path / "c"),
                                           every=3))
    logs = []
    res = run(spec, program=_flaky_program(spec, 6), log_fn=logs.append)
    assert any("restored step 3" in m for m in logs)
    assert int(res.opt_state.step) == 7

    clean = run(_spec(total=7, eval=EvalSpec(every=2, n_batches=1)),
                log_fn=lambda s: None)
    import jax
    for a, b in zip(jax.tree.leaves((res.params, res.opt_state)),
                    jax.tree.leaves((clean.params, clean.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # history is the uninterrupted record: no duplicated steps, and the
    # rewound eval stream reproduces the clean eval curve exactly
    assert res.history["step"] == clean.history["step"] == list(range(7))
    np.testing.assert_allclose(res.history["loss"], clean.history["loss"])
    assert res.history["eval_step"] == clean.history["eval_step"]
    np.testing.assert_allclose(res.history["eval_loss"],
                               clean.history["eval_loss"])


def test_eval_stream_deterministic_across_resume(tmp_path):
    """The default eval stream fast-forwards on checkpoint resume: a
    resumed run's eval curve equals the uninterrupted run's tail."""
    ck = str(tmp_path / "ck")
    clean = run(_spec(total=6, eval=EvalSpec(every=2, n_batches=2)),
                log_fn=lambda s: None)
    run(_spec(total=4, eval=EvalSpec(every=2, n_batches=2),
              checkpoint=CheckpointSpec(dir=ck, every=4)),
        log_fn=lambda s: None)
    res = run(_spec(total=6, eval=EvalSpec(every=2, n_batches=2),
                    checkpoint=CheckpointSpec(dir=ck, every=4,
                                              resume=True)),
              log_fn=lambda s: None)
    assert res.start_step == 4
    assert res.history["eval_step"] == [5]
    np.testing.assert_allclose(res.history["eval_loss"],
                               clean.history["eval_loss"][2:])


def test_transient_failure_without_checkpoint_raises():
    from jax.errors import JaxRuntimeError
    from repro.run import build_step_program
    spec = _spec(total=3)
    prog = build_step_program(spec)

    def step(params, opt_state, batch, hp):
        raise JaxRuntimeError("no checkpoint to recover from")

    prog.step = step
    with pytest.raises(JaxRuntimeError):
        run(spec, program=prog, log_fn=lambda s: None)


# ---------------------------------------------------------------------
# Default pipeline assembly
# ---------------------------------------------------------------------

def test_default_pipeline_order_and_replacement():
    mine = StragglerHook()
    res = run(_spec(total=1,
                    fault=FaultSpec(heartbeat_timeout_s=60.0),
                    log_every=5),
              hooks=(mine,), log_fn=lambda s: None)
    kinds = [type(h).__name__ for h in res.hooks]
    # measurement before side effects; user instance replaces the default
    assert kinds == ["HeartbeatHook", "HistoryHook", "LoggingHook",
                    "StragglerHook"]
    assert res.find_hook(StragglerHook) is mine
    assert len(mine.monitor.events) == 0  # observed, no stragglers flagged
    hb = res.find_hook(HeartbeatHook)
    assert hb.heartbeat is not None and not hb.heartbeat.stalled


def test_checkpoint_hook_and_resume_through_run(tmp_path):
    ck = str(tmp_path / "ck")
    spec = _spec(total=4, checkpoint=CheckpointSpec(dir=ck, every=2))
    res = run(spec, log_fn=lambda s: None)
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(ck)
    assert mgr.latest_step() == 4

    # a second run with resume=True and a longer horizon continues at 4
    spec2 = _spec(total=6, checkpoint=CheckpointSpec(dir=ck, every=2,
                                                     resume=True))
    res2 = run(spec2, log_fn=lambda s: None)
    assert res2.start_step == 4
    assert res2.history["step"] == [4, 5]
    # ...and the resumed trajectory equals the uninterrupted one
    res_full = run(_spec(total=6), log_fn=lambda s: None)
    np.testing.assert_allclose(res2.history["loss"],
                               res_full.history["loss"][4:], rtol=1e-5)


# ---------------------------------------------------------------------
# Acceptance: zero steady-state recompiles with the full pipeline
# ---------------------------------------------------------------------

def test_full_hook_pipeline_zero_recompiles(tmp_path):
    """6 steps with cosine-scheduled hparams + history + logging + eval +
    checkpoint + heartbeat hooks: the jitted step compiles exactly once.
    Hooks are host-side observers — they can never retrace the program."""
    spec = RunSpec(
        model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
        data=DataConfig(vocab=0, seq_len=32, global_batch=4),
        opt=OptSpec(name="adalomo", lr=1e-3, schedule="cosine",
                    hparams={"weight_decay": 0.01}),
        steps=StepSpec(total=6),
        checkpoint=CheckpointSpec(dir=str(tmp_path / "ck"), every=2),
        eval=EvalSpec(every=3, n_batches=1),
        fault=FaultSpec(heartbeat_timeout_s=60.0),
        log_every=2)
    res = run(spec, log_fn=lambda s: None)
    assert res.program.cache_size() == 1, \
        "hook pipeline / hparam schedule recompiled the train step"
    # the lr actually changed every step (schedule ran as data)
    assert len(set(res.history["lr"])) == len(res.history["lr"])
    assert res.find_hook(CheckpointHook) is not None
    assert res.find_hook(HistoryHook) is not None


def test_microbatched_run_zero_recompiles():
    spec = _spec(total=4, steps=StepSpec(total=4, microbatches=2),
                 data=DataConfig(vocab=0, seq_len=32, global_batch=4))
    res = run(spec, log_fn=lambda s: None)
    assert res.program.cache_size() == 1
    assert int(res.opt_state.step) == 8  # k sequential updates per step


def test_history_matches_trainer_shim():
    """The Trainer compat shim and bare run() produce identical curves —
    the migration is semantics-preserving."""
    import jax
    from repro.data.pipeline import batches
    from repro.models.registry import get_arch
    from repro.train.loop import TrainConfig, Trainer
    arch = get_arch("h2o-danube-1.8b", smoke=True)
    spec = _spec(total=3)
    res = run(spec, log_fn=lambda s: None)

    tcfg = TrainConfig(optimizer="adalomo", lr=1e-3, total_steps=3,
                       schedule="constant", log_every=0)
    tr = Trainer(arch, tcfg, log_fn=lambda s: None)
    params, state = tr.init(0)
    dcfg = DataConfig(vocab=arch.cfg.vocab, seq_len=32, global_batch=4)
    out = tr.fit(params, state, batches(dcfg))
    np.testing.assert_allclose(out["history"]["loss"],
                               res.history["loss"], rtol=1e-6)
