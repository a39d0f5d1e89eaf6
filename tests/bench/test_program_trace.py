"""The program's side of a trace (``bench/program_trace.py``) and the five
per-layer metrics that read it: shares of the training step's device
time by named scope, the scheduler's queue wait and the prefill stall,
on hand-built traces with known answers, and the scope of each device op
read from the committed v5e trace."""
import shutil
import statistics
import tempfile
from pathlib import Path

import pytest

from bench import harness, program_trace, tracefile
from bench.program_trace import ProgramTrace, Span
from bench.tracefile import Event, Trace

DATA = Path(__file__).parent / "data"
STEP = "jit(one_step)"
BODY = f"{STEP}/bwd/while/body/closed_call"

# (op, start ns, duration ns, tf_op) of two runs of the training step,
# [0, 100) and [200, 300), and one op between them
OPS = [
    ("%fusion.1 = f32[] fusion()", 0, 10,
     f"{STEP}/fwd/while/body/closed_call/attention/dot_general:"),
    ("%fusion.2 = f32[] fusion()", 10, 10,
     f"{STEP}/fwd/while/body/closed_call/mlp/dot_general:"),
    ("%while.3 = f32[] while()", 20, 60, f"{STEP}/bwd/while:"),
    ("%fusion.4 = f32[] fusion()", 20, 10,
     f"{BODY}/recompute/jvp(attention)/dot_general:"),
    ("%fusion.5 = f32[] fusion()", 30, 20,
     f"{BODY}/grad/transpose(jvp(attention))/dot_general:"),
    ("%adalomo_update.6 = f32[] custom-call()", 50, 10,
     f"{BODY}/update/jit(adalomo_update)/pallas_call:"),
    ("%fusion.7 = f32[] fusion()", 60, 10, f"{BODY}/recompute/jvp(mlp)/mul:"),
    ("%fusion.8 = f32[] fusion()", 90, 5, f"{STEP}/head/update/mul:"),
    ("%copy.9 = f32[] copy()", 95, 5, ""),
    ("%fusion.10 = f32[] fusion()", 150, 20, "jit(other)/dot_general:"),
    # the pullback of the recomputed attention, as the TPU trace names it
    ("%fusion.11 = f32[] fusion()", 200, 40,
     f"{BODY}/grad/transpose(recompute)/jvp(attention)/dot_general:"
     f";{BODY}/grad/transpose(jvp())/reshape:"),
    ("%fusion.12 = f32[] fusion()", 250, 10, "update/mul:"),
]


def _train():
    trace = Trace(ops=[[Event(n, s, d) for n, s, d, _ in OPS]],
                  modules=[[Event("jit_one_step", 0, 100),
                            Event("jit_other", 150, 20),
                            Event("jit_one_step", 200, 100)]],
                  spans=[], window=(0, 400))
    return ProgramTrace(trace=trace, spans=[],
                        scopes=[[t for *_, t in OPS]])


# busy inside the two runs: [0, 100) less the idle [80, 90), then
# [200, 240) and [250, 260)
BUSY = 90 + 40 + 10


@pytest.mark.parametrize("metric,device_ns", [
    ("recompute_share.train", 10 + 10),
    ("attention_share.train", 10 + 10 + 20 + 40),
    ("fused_update_share.train", 10 + 5 + 10),
])
def test_train_shares_by_scope(metric, device_ns):
    pt = _train()
    got = harness.metric_reader(metric)(pt.trace, {"kind": "train"})
    assert got == pytest.approx(100.0 * device_ns / BUSY)
    assert harness.metric_reader(metric)(pt.trace, {"kind": "serve"}) is None


def test_scope_breakdown_splits_the_step():
    bd = _train().scope_breakdown()
    by = {k: v * 1e9 for k, v in bd["by_scope_s"].items()}
    assert bd["runs"] == 2 and bd["busy_s"] * 1e9 == pytest.approx(BUSY)
    assert by["fwd"] == pytest.approx(20)
    assert by["bwd"] == pytest.approx(10 + 20 + 10 + 10 + 40)
    assert by["bwd/recompute"] == pytest.approx(20)
    assert by["bwd/grad"] == pytest.approx(60)
    assert by["bwd/update"] == pytest.approx(10)
    assert by["head"] == pytest.approx(5)
    assert by["attention"] == pytest.approx(80)
    assert by["bwd/grad|attention"] == pytest.approx(60)
    assert by["update"] == pytest.approx(25)
    # the copy and the unscoped update op fall outside fwd/bwd/head
    assert by["(none)"] == pytest.approx(15)
    assert dict(bd["outside_s"]) == pytest.approx({"copy": 5e-9,
                                                   "fusion": 10e-9})


def test_a_program_without_scopes_reads_nothing():
    pt = _train()
    bare = ProgramTrace(trace=pt.trace, spans=[],
                        scopes=[[""] * len(OPS)])
    for metric in ("recompute_share.train", "attention_share.train",
                   "fused_update_share.train"):
        assert harness.metric_reader(metric)(
            bare.trace, {"kind": "train"}) is None


def _serve():
    ms = 1e6
    spans = [
        Span("repro.serve.submit", 0, 1 * ms, {"rid": 0}),
        Span("repro.serve.submit", 5 * ms, 1 * ms, {"rid": 1}),
        Span("repro.serve.step", 10 * ms, 40 * ms),
        Span("repro.serve.prefill", 11 * ms, 10 * ms, {"rid": 0}),
        Span("repro.serve.prefill", 22 * ms, 8 * ms, {"rid": 1}),
        Span("repro.serve.step", 60 * ms, 30 * ms),
        # rid 1 again, after a preemption: its wait counts once
        Span("repro.serve.prefill", 65 * ms, 5 * ms, {"rid": 1}),
        # submitted before the trace began: no wait to read
        Span("repro.serve.prefill", 71 * ms, 4 * ms, {"rid": 9}),
        # admitted after the window
        Span("repro.serve.submit", 80 * ms, 1 * ms, {"rid": 2}),
        Span("repro.serve.step", 95 * ms, 20 * ms),
        Span("repro.serve.prefill", 101 * ms, 10 * ms, {"rid": 2}),
    ]
    trace = Trace(ops=[[]], modules=[[]], spans=[], window=(0, 100 * ms))
    return ProgramTrace(trace=trace, spans=spans, scopes=[[]])


def test_queue_wait_is_submit_end_to_first_prefill():
    pt = _serve()
    assert pt.queue_waits() == pytest.approx({0: 10.0, 1: 16.0})
    got = harness.metric_reader("queue_wait_ms.serve")(
        pt.trace, {"kind": "serve"})
    assert got == pytest.approx(statistics.median([10.0, 16.0]))


def test_prefill_stall_is_prefill_over_step_time_in_window():
    pt = _serve()
    got = harness.metric_reader("prefill_stall_share.serve")(
        pt.trace, {"kind": "serve"})
    # prefills 10 + 8 + 5 + 4 (+ 0 of the one past the window) ms,
    # steps 40 + 30 + 5 (clipped at the window's end) ms
    assert got == pytest.approx(100.0 * 27 / 75)
    assert harness.metric_reader("prefill_stall_share.serve")(
        pt.trace, {"kind": "train"}) is None


def test_serve_metrics_need_the_program_spans():
    trace = Trace(ops=[[]], modules=[[]], spans=[], window=(0, 10))
    ProgramTrace(trace=trace, spans=[], scopes=[[]])
    for metric in ("queue_wait_ms.serve", "prefill_stall_share.serve"):
        assert harness.metric_reader(metric)(trace, {"kind": "serve"}) \
            is None


def test_idle_gaps_take_the_innermost_span_of_either_side():
    ms = 1e6
    trace = Trace(ops=[[Event("%a.1 = f32[] a()", 0, 10 * ms),
                        Event("%b.2 = f32[] b()", 20 * ms, 10 * ms),
                        Event("%c.3 = f32[] c()", 40 * ms, 10 * ms)]],
                  modules=[[]],
                  spans=[Event("bench.serve.step", 0, 50 * ms)],
                  window=(0, 50 * ms))
    pt = ProgramTrace(trace=trace, spans=[
        Span("repro.serve.step", 1 * ms, 48 * ms),
        Span("repro.serve.admit", 11 * ms, 8 * ms)], scopes=[[]])
    assert [name for name, _ in pt.idle_gaps(0, 2)] == [
        "repro.serve.admit", "repro.serve.step"]
    # the harness's own naming sees only the benchmark's span
    assert [name for name, _ in trace.idle_gaps(0, 2)] == [
        "bench.serve.step", "bench.serve.step"]


def test_scope_of_each_device_op_from_the_v5e_trace():
    tr = tracefile.load(DATA / "kernels.xplane.pb", span_prefix="probe.")
    before = {m: harness.metric_reader(m)(tr, {"kind": "train"})
              for m in ("update_share.train", "idle_share.train")}
    pt = program_trace.load(DATA / "kernels.xplane.pb", tr)
    assert len(pt.scopes[0]) == len(tr.ops[0])
    by_kind = {}
    for e, scope in zip(tr.ops[0], pt.scopes[0]):
        by_kind.setdefault(tracefile.op_kind(e.name), set()).add(scope)
    assert by_kind["adalomo_update"] == {
        "jit(f)/jit(adalomo_update)/pallas_call:"}
    assert by_kind["copy-start"] == {""}
    assert program_trace.scope_path(
        "jit(f)/jit(adalomo_update)/pallas_call:") == (
        "jit(f)", "jit(adalomo_update)")
    # reading the scopes leaves the harness's reduction as it was
    assert {m: harness.metric_reader(m)(tr, {"kind": "train"})
            for m in before} == before


@pytest.mark.parametrize("part,hit", [
    ("attention", True), ("jvp(attention)", True),
    ("transpose(jvp(attention))", True), ("attention_mask", False),
    ("jit(_where)", False), ("my_attention", False)])
def test_under_matches_a_scope_and_its_transformations(part, hit):
    assert program_trace.under("attention")(("jit(f)", part)) is hit


# --------------------------------------------------------------------------
# A v5e trace with the program's spans and scopes
# (tests/bench/record_program_trace.py: one 2-layer full-width training
# step through run(), then a PagedEngine serving four requests)
# --------------------------------------------------------------------------

PROGRAM = DATA / "program.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    return program_trace.load(PROGRAM, tracefile.load(PROGRAM))


@pytest.mark.parametrize("metric,kind", [
    ("recompute_share.train", "train"), ("attention_share.train", "train"),
    ("fused_update_share.train", "train"), ("queue_wait_ms.serve", "serve"),
    ("prefill_stall_share.serve", "serve")])
def test_the_readers_read_the_chip_trace(chip, metric, kind):
    got = harness.metric_reader(metric)(chip.trace, {"kind": kind})
    assert got is not None and got > 0
    if metric.endswith("share.train") or metric.endswith("share.serve"):
        assert got < 100


def test_program_spans_and_device_programs_share_one_clock(chip):
    tr = chip.trace
    chunks = chip.named("repro.serve.chunk")
    runs = tr.modules_named("jit_chunk")
    assert runs
    for m in runs:
        assert any(c.start <= m.start <= c.end for c in chunks)
    dispatches = chip.named("repro.train.dispatch")
    steps = chip.named("repro.train.step")
    runs = tr.modules_named("jit_one_step")
    assert runs
    for m in runs:
        d = max((d for d in dispatches if d.start <= m.start),
                key=lambda d: d.start)
        (step,) = [s for s in steps if s.start <= d.start <= s.end]
        assert m.start <= step.end


def test_the_step_falls_under_its_scopes(chip):
    bd = chip.scope_breakdown()
    by = bd["by_scope_s"]
    top = sum(by.get(k, 0.0) for k in ("fwd", "bwd", "head"))
    assert top >= 0.95 * bd["ops_s"]
    assert by["bwd/recompute"] > 0 and by["bwd/grad"] > 0
    assert by["bwd/update"] > 0 and by["attention"] > 0
    # the kernel is under the update scope
    kernel = harness.metric_reader("update_share.train")(
        chip.trace, {"kind": "train"})
    fused = harness.metric_reader("fused_update_share.train")(
        chip.trace, {"kind": "train"})
    assert fused >= kernel


def test_chip_trace_names_idle_gaps_by_program_spans(chip):
    names = [n for n, _ in chip.idle_gaps(0, 10)]
    assert any(n.startswith("repro.") for n in names)


def test_a_reader_finds_the_trace_file_of_its_run(tmp_path, monkeypatch):
    """As bench/run.py leaves it: the trace under a bench-trace-* dir of
    the temp directory, told apart from others by its window span."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    run_dir = tmp_path / "bench-trace-1" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    shutil.copyfile(PROGRAM, run_dir / "host.xplane.pb")
    other = tmp_path / "bench-trace-2"
    other.mkdir()
    shutil.copyfile(DATA / "kernels.xplane.pb", other / "host.xplane.pb")
    tr = tracefile.load(tmp_path / "bench-trace-1")
    pt = program_trace.of(tr)
    assert pt is not None and pt.trace is tr
    assert pt.named("repro.serve.prefill") and pt.scopes[0]
    assert harness.metric_reader("attention_share.train")(
        tr, {"kind": "train"}) > 0
    # a trace whose window no run file holds reads nothing
    lost = tracefile.load(DATA / "kernels.xplane.pb", span_prefix="probe.")
    assert program_trace.of(lost) is None
