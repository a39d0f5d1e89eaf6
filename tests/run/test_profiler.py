"""ProfilerHook: a jax profiler trace for a configurable step window,
stamped with the RunSpec that produced it."""
import json
import types

import pytest

from repro.data.pipeline import DataConfig
from repro.run import (CheckpointSpec, ModelSpec, OptSpec, ProfileSpec,
                       ProfilerHook, RunSpec, StepSpec, run)


def _spec(total=4, **kw):
    base = dict(
        model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
        data=DataConfig(vocab=0, seq_len=32, global_batch=4),
        opt=OptSpec(name="adalomo", lr=1e-3, schedule="constant"),
        steps=StepSpec(total=total),
        log_every=0)
    base.update(kw)
    return RunSpec(**base)


def test_profile_spec_roundtrip():
    spec = _spec(profile=ProfileSpec(dir="/tmp/prof", start=2, steps=3))
    back = RunSpec.from_json(spec.to_json())
    assert back.profile == spec.profile
    assert back == spec


def test_profiler_traces_window_and_stamps_spec(tmp_path):
    spec = _spec(profile=ProfileSpec(dir=str(tmp_path / "prof"),
                                     start=1, steps=2))
    res = run(spec, log_fn=lambda s: None)

    hook = res.find_hook(ProfilerHook)
    assert hook is not None
    # registered by the default pipeline, before HistoryHook
    kinds = [type(h).__name__ for h in res.hooks]
    assert kinds.index("ProfilerHook") < kinds.index("HistoryHook")
    # window executed and closed
    assert hook.done and not hook.active

    prof = tmp_path / "prof"
    # RunSpec sidecar: the trace is attributable to its exact spec
    sidecar = json.loads((prof / "profile.runspec.json").read_text())
    assert RunSpec.from_dict(sidecar) == spec
    # the trace itself landed (plugins/... tensorboard layout)
    produced = [p for p in prof.iterdir()
                if p.name != "profile.runspec.json"]
    assert produced, list(prof.iterdir())
    # tracing must not add steady-state recompiles
    assert res.program.cache_size() == 1


def test_profiler_skips_window_already_executed(tmp_path):
    hook = ProfilerHook(tmp_path / "prof", start=1, steps=2)
    spec = _spec()
    ctx = types.SimpleNamespace(spec=spec, start_step=3,
                                log=lambda s: None)
    hook.on_run_start(ctx)
    assert hook.done and not hook.active
    # step events after a skipped window never (re)start a trace
    hook.on_step_end(ctx, types.SimpleNamespace(step=3))
    assert not hook.active


def test_profiler_user_instance_replaces_default(tmp_path):
    mine = ProfilerHook(tmp_path / "mine", start=1, steps=1)
    spec = _spec(profile=ProfileSpec(dir=str(tmp_path / "default")))
    res = run(spec, hooks=(mine,), log_fn=lambda s: None)
    profilers = [h for h in res.hooks if isinstance(h, ProfilerHook)]
    assert profilers == [mine]
    assert not (tmp_path / "default").exists()


def test_profiler_absent_without_profile_dir(tmp_path):
    res = run(_spec(total=1,
                    checkpoint=CheckpointSpec(dir=str(tmp_path), every=1)),
              log_fn=lambda s: None)
    assert res.find_hook(ProfilerHook) is None


def test_profiler_failure_to_start_raises(tmp_path, monkeypatch):
    """A requested trace that cannot start fails loudly: a run that
    silently drops its trace would pass off an untraced window."""
    import jax.profiler

    def broken(*a, **kw):
        raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    hook = ProfilerHook(tmp_path / "prof", start=0, steps=1)
    ctx = types.SimpleNamespace(spec=_spec(), start_step=0,
                                log=lambda s: None)
    with pytest.raises(RuntimeError, match="profiler backend unavailable"):
        hook.on_run_start(ctx)
    assert not hook.active
