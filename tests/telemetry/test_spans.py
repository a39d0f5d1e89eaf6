"""The program's host spans and named scopes, read back from a profiler
trace on the CPU: ``run()`` and ``PagedEngine`` emit every ``repro.*``
span with its arguments, a request's spans share its ``rid``, and the
fused step's device program carries the fwd / bwd (recompute, grad,
update) / head scopes crossed by attention and MLP."""
import glob

import jax
import pytest
from jax.errors import JaxRuntimeError
from jax.profiler import ProfileData

from benchmarks.common import tiny_llama
from repro.data.pipeline import DataConfig
from repro.run import (CheckpointSpec, FaultSpec, ModelSpec, OptSpec,
                       RunSpec, StepSpec, build_step_program, run)
from repro.serve.engine import PagedEngine, PagedServeConfig
from repro.telemetry import span, step_span

B, S = 4, 32


def _spec(total=3, **kw):
    base = dict(
        model=ModelSpec(arch="h2o-danube-1.8b", smoke=True),
        data=DataConfig(vocab=0, seq_len=S, global_batch=B),
        opt=OptSpec(name="adalomo", lr=1e-3, schedule="constant"),
        steps=StepSpec(total=total), log_every=0)
    base.update(kw)
    return RunSpec(**base)


def _traced(tmp_path, fn):
    """Run fn under a profiler trace; the repro.* host spans it left, as
    (name, start_ns, end_ns, args) in start order."""
    out = str(tmp_path / "trace")
    jax.profiler.start_trace(out)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_helpers_carry_arguments(tmp_path):
    def go():
        with step_span("repro.test.step", 3, tokens=128):
            with span("repro.test.inner", rid=7) as sp:
                sp.set_metadata(finished=2)

    spans = _traced(tmp_path, go)
    (outer,), (inner,) = (_named(spans, "repro.test.step"),
                          _named(spans, "repro.test.inner"))
    assert outer[3]["step_num"] == 3 and outer[3]["tokens"] == 128
    assert inner[3] == {"rid": 7, "finished": 2}
    assert _inside(inner, outer)


def test_run_emits_the_training_spans(tmp_path):
    spans = _traced(tmp_path,
                    lambda: run(_spec(total=3), log_fn=lambda s: None))
    steps = _named(spans, "repro.train.step")
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2]
    for name in ("batch", "dispatch", "sync", "hooks"):
        got = _named(spans, f"repro.train.{name}")
        assert len(got) == 3, name
        # one of each inside each step's span, in loop order
        assert all(_inside(g, s) for g, s in zip(got, steps)), name
    for name in ("dispatch", "sync", "hooks"):
        assert [s[3]["step"] for s in _named(
            spans, f"repro.train.{name}")] == [0, 1, 2]
    assert all(s[3]["tokens"] == B * S
               for s in _named(spans, "repro.train.dispatch"))
    order = [_named(spans, f"repro.train.{n}")[1][1]
             for n in ("batch", "dispatch", "sync", "hooks")]
    assert order == sorted(order)
    assert not _named(spans, "repro.train.recover")


def test_a_recovered_fault_leaves_a_recover_span(tmp_path):
    spec = _spec(total=4, checkpoint=CheckpointSpec(dir=str(tmp_path / "ck"),
                                                    every=1),
                 fault=FaultSpec(retries=1))
    prog = build_step_program(spec)
    real, calls = prog.step, {"n": 0}

    def flaky(*a):
        out = real(*a)
        calls["n"] += 1
        if calls["n"] == 3:
            raise JaxRuntimeError("injected transient fault")
        return out

    prog.step = flaky
    spans = _traced(tmp_path, lambda: run(spec, program=prog,
                                          log_fn=lambda s: None))
    (rec,) = _named(spans, "repro.train.recover")
    assert rec[3]["step"] == 2
    failed = [s for s in _named(spans, "repro.train.step")
              if s[3]["step_num"] == 2][0]
    assert _inside(rec, failed)


@pytest.fixture(scope="module")
def tiny():
    arch = tiny_llama(layers=2, d=64)
    return arch, arch.init_params(jax.random.PRNGKey(0))


def test_engine_emits_the_serving_spans(tmp_path, tiny):
    arch, params = tiny
    eng = PagedEngine(arch, params, PagedServeConfig(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        chunk=4, max_new_tokens=6, bucket_min=8))
    eng.warmup([3, 12])
    prompts = [[5, 17, 23], [7] * 12, [3, 4, 5, 6, 7]]
    out = {}
    spans = _traced(tmp_path,
                    lambda: out.update(tokens=eng.generate(prompts)))
    submits = _named(spans, "repro.serve.submit")
    assert [(s[3]["rid"], s[3]["prompt_tokens"]) for s in submits] == [
        (0, 3), (1, 12), (2, 5)]
    prefills = _named(spans, "repro.serve.prefill")
    assert sorted(p[3]["rid"] for p in prefills) == sorted(
        s[3]["rid"] for s in submits)
    for p in prefills:
        sub = [s for s in submits if s[3]["rid"] == p[3]["rid"]][0]
        assert p[1] >= sub[2]       # admitted after it was queued
        assert p[3]["tokens"] == sub[3]["prompt_tokens"]
        assert p[3]["bucket"] in (8, 16)
    steps = _named(spans, "repro.serve.step")
    assert steps
    for name in ("expire", "admit", "prefill", "ensure_ahead", "chunk",
                 "collect"):
        got = _named(spans, f"repro.serve.{name}")
        assert got, name
        assert all(any(_inside(g, s) for s in steps) for g in got), name
    chunks = _named(spans, "repro.serve.chunk")
    # two slots, three requests: the third waits for a free slot
    assert max(c[3]["live"] for c in chunks) == 2
    emitted = sum(c[3]["tokens"] for c in chunks)
    # each prefill gives a request its first token, the chunks the rest
    assert emitted + len(prompts) == sum(map(len, out["tokens"]))
    assert sum(c[3]["finished"] for c in _named(
        spans, "repro.serve.collect")) == len(prompts)
    assert all(e[3]["preempted"] == 0
               for e in _named(spans, "repro.serve.ensure_ahead"))


def test_fused_step_names_its_phases():
    spec = _spec(total=3)
    prog = build_step_program(spec)
    text = prog.lower().as_text(debug_info=True)
    for scope in ("recompute/jvp(attention)",
                  "grad/transpose(jvp(attention))",
                  "recompute/jvp(mlp)", "grad/transpose(jvp(mlp))",
                  "update/", "/fwd", "/bwd", "/head"):
        assert scope in text, scope
    # names are metadata: the program still compiles once
    run(spec, program=prog, log_fn=lambda s: None)
    assert prog.cache_size() == 1
