"""What the program writes into a profiler trace, beside what
``bench/tracefile.py`` reduces: its host spans (``repro.*``, with their
arguments: ``rid``, ``tokens``, ``step`` ...) and each device op's
named-scope path, the ``tf_op`` stat of the op's event metadata
(``jit(one_step)/bwd/while/body/closed_call/recompute/jvp(attention)/
while/body/dot_general:``), which ``jax.profiler.ProfileData`` does not
expose.  ``.xplane.pb`` is decoded with a schema written out here, so
nothing imports TensorFlow.

A per-layer reader gets only the harness's :class:`~bench.tracefile.Trace`;
:func:`of` finds the ``.xplane.pb`` that trace was reduced from (the
run's ``bench-trace-*`` directory, on disk while the readers run, told
apart by its ``bench.trace_window`` span) and returns ``None`` where there
is none, or where the program writes no spans or scopes.

    python3 bench/program_trace.py <trace.xplane.pb>

prints the breakdown by scope, the program's spans and the idle gaps named
by the innermost ``bench.*`` or ``repro.*`` span.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import json
import os
import re
import statistics
import sys
import tempfile
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import tracefile  # noqa: E402
from bench.tracefile import CONTAINERS, WINDOW_SPAN, Event, Trace  # noqa: E402

SPAN_PREFIX = "repro."
STEP_MODULE = "jit_one_step"
TOP_SCOPES = ("fwd", "bwd", "head")
BWD_PHASES = ("recompute", "grad", "update")


@dataclasses.dataclass
class Span(Event):
    args: dict = dataclasses.field(default_factory=dict)


# --------------------------------------------------------------------------
# The .xplane.pb schema (tsl/profiler/protobuf/xplane.proto), the fields
# read here
# --------------------------------------------------------------------------

@functools.cache
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, f64 = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    text, raw, msg = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if tname:
                f.type_name = ".bench_xplane." + tname
        return m

    message(fd.message_type, "XSpace",
            [("planes", 1, msg, many, "XPlane")])
    plane = message(fd.message_type, "XPlane", [
        ("id", 1, i64, one, None), ("name", 2, text, one, None),
        ("lines", 3, msg, many, "XLine"),
        ("event_metadata", 4, msg, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, many, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry,
                    [("key", 1, i64, one, None),
                     ("value", 2, msg, one, value)])
        e.options.map_entry = True
    message(fd.message_type, "XLine", [
        ("id", 1, i64, one, None), ("name", 2, text, one, None),
        ("events", 4, msg, many, "XEvent")])
    message(fd.message_type, "XEvent", [
        ("metadata_id", 1, i64, one, None)])
    message(fd.message_type, "XStat", [
        ("metadata_id", 1, i64, one, None),
        ("double_value", 2, f64, one, None),
        ("uint64_value", 3, u64, one, None),
        ("int64_value", 4, i64, one, None),
        ("str_value", 5, text, one, None),
        ("bytes_value", 6, raw, one, None),
        ("ref_value", 7, u64, one, None)])
    message(fd.message_type, "XEventMetadata", [
        ("id", 1, i64, one, None), ("name", 2, text, one, None),
        ("stats", 5, msg, many, "XStat")])
    message(fd.message_type, "XStatMetadata", [
        ("id", 1, i64, one, None), ("name", 2, text, one, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def device_op_scopes(path) -> list[list[tuple[str, str]]]:
    """Per TPU device (in ``Trace.ops`` order), per event of its
    "XLA Ops" line in file order: (op text, ``tf_op`` or "")."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    devices = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        tf_op = [k for k, v in plane.stat_metadata.items()
                 if v.name == "tf_op"]
        meta = plane.event_metadata
        scope_of = {k: next((_text(plane, s) for s in md.stats
                             if s.metadata_id in tf_op), "")
                    for k, md in meta.items()}
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(meta[e.metadata_id].name, scope_of[e.metadata_id])
                       for e in line.events]
        devices.append((int(plane.name.rsplit(":", 1)[1]), ops))
    return [ops for _, ops in sorted(devices)]


def _text(plane, stat) -> str:
    """A string stat, stored as itself or by reference to a name."""
    if stat.ref_value:
        return plane.stat_metadata[stat.ref_value].name
    return stat.str_value


# --------------------------------------------------------------------------
# Scope paths
# --------------------------------------------------------------------------

def scope_path(tf_op: str) -> tuple[str, ...]:
    """The named scopes of an op: its ``tf_op`` (the first, where a fusion
    lists several after ``;``) without the last part (the primitive)."""
    return tuple(tf_op.split(";", 1)[0].split("/")[:-1])


def under(name: str):
    """A test of a scope path: has the scope ``name``, as itself or
    under a transformation (``jvp(attention)``,
    ``transpose(jvp(attention))``)."""
    rx = re.compile(r"(?:[\w.]+\()*" + re.escape(name) + r"\)*")
    return lambda path: any(rx.fullmatch(p) for p in path)


def phase(path: tuple[str, ...]) -> str:
    """recompute, grad or update: the first of them in the path, by its
    own name.  The pullback of an op renames its scope (the backward of
    attention runs under ``grad/transpose(recompute)/jvp(attention)``),
    so a transformed name never counts."""
    return next((p for p in path if p in BWD_PHASES), "")


def in_phase(name: str):
    """A test of a scope path: its phase is ``name``."""
    return lambda path: phase(path) == name


def top_scope(path: tuple[str, ...]) -> str:
    """fwd, bwd or head for an op of the training step; "" outside them.
    The per-layer phases lie in bwd alone."""
    for p in path:
        if p in TOP_SCOPES:
            return p
    if any(p in ("recompute", "grad") for p in path):
        return "bwd"
    return ""


# --------------------------------------------------------------------------
# The program's side of a trace
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    trace: Trace
    spans: list          # [Span] host spans named repro.*, start order
    scopes: list         # per device: tf_op of each op of trace.ops[d]

    def __post_init__(self):
        # what of(trace) finds again
        self.trace.program = self

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    # ------------------------------------------------ device time by scope
    def step_ops(self, module: str = STEP_MODULE, device: int = 0):
        """(op, scope path) of the ops inside the runs of ``module`` whole
        in the window, loops left out (their children carry the time),
        and those runs."""
        if device >= self.trace.n_devices:
            return [], []
        mods = self.trace.modules_named(module, device)
        ops = [(e, scope_path(tf_op)) for e, tf_op in zip(
                   self.trace.ops[device], self.scopes[device])
               if tracefile.op_kind(e.name) not in CONTAINERS
               and any(m.start <= e.start and e.end <= m.end
                       for m in mods)]
        return ops, mods

    def step_busy_ns(self, module: str = STEP_MODULE, device: int = 0
                     ) -> float:
        """Union of the op intervals inside the runs of ``module``."""
        if device >= self.trace.n_devices:
            return 0.0
        mods = self.trace.modules_named(module, device)
        total = 0.0
        for a, b in self.trace.busy_intervals(device):
            for m in mods:
                total += max(0.0, min(b, m.end) - max(a, m.start))
        return total

    def scope_share(self, test, module: str = STEP_MODULE):
        """Percent of the busy time of ``module``'s runs spent in ops
        whose scope path passes ``test``; None where none does."""
        ops, _ = self.step_ops(module)
        busy = self.step_busy_ns(module)
        t = sum(e.dur for e, path in ops if test(path))
        if busy <= 0 or t <= 0:
            return None
        return 100.0 * t / busy

    def scope_breakdown(self, module: str = STEP_MODULE) -> dict:
        """Seconds of the step's busy time by top scope, by phase of the
        reverse scan, and attention / MLP within each; op kinds of what
        falls outside every scope."""
        ops, mods = self.step_ops(module)
        att, mlp = under("attention"), under("mlp")
        by = collections.defaultdict(float)
        outside = collections.defaultdict(float)
        for e, path in ops:
            top = top_scope(path) or "(none)"
            keys = [top]
            if top == "bwd":
                keys.append(f"bwd/{phase(path) or '(loop)'}")
            part = "attention" if att(path) else "mlp" if mlp(path) else None
            if part:
                keys += [f"{k}|{part}" for k in keys] + [part]
            if phase(path) == "update":
                keys.append("update")
            for k in keys:
                by[k] += e.dur
            if top == "(none)":
                outside[tracefile.op_kind(e.name)] += e.dur
        busy = self.step_busy_ns(module)
        return {"runs": len(mods), "busy_s": busy * 1e-9,
                "ops_s": sum(e.dur for e, _ in ops) * 1e-9,
                "by_scope_s": {k: v * 1e-9 for k, v in sorted(by.items())},
                "outside_s": [[k, v * 1e-9] for k, v in sorted(
                    outside.items(), key=lambda kv: -kv[1])[:10]]}

    # ----------------------------------------------------- host spans
    def in_window(self, s: Event) -> bool:
        w0, w1 = self.trace.window
        return w0 <= s.start < w1

    def queue_waits(self) -> dict:
        """Per request whose first ``repro.serve.prefill`` starts in the
        window and whose ``repro.serve.submit`` is in the trace: ms from
        the end of the submit to the start of that prefill, by rid."""
        submitted = {s.args.get("rid"): s.end
                     for s in self.named("repro.serve.submit")}
        first = {}
        for p in self.named("repro.serve.prefill"):
            rid = p.args.get("rid")
            if rid not in first or p.start < first[rid].start:
                first[rid] = p
        return {rid: (p.start - submitted[rid]) * 1e-6
                for rid, p in first.items()
                if rid in submitted and self.in_window(p)}

    def span_seconds(self, name: str) -> float:
        """Seconds of the spans ``name`` inside the window."""
        return sum(self.trace._clip(s) for s in self.named(name)) * 1e-9

    def idle_gaps(self, device: int = 0, n: int = 10
                  ) -> list[tuple[str, float]]:
        """``Trace.idle_gaps``, each gap named by the innermost span of
        the benchmark or of the program open at its middle."""
        both = dataclasses.replace(self.trace,
                                   spans=self.trace.spans + self.spans)
        return both.idle_gaps(device, n)

    def span_summary(self) -> dict:
        """Count and seconds inside the window of each program span."""
        out = collections.defaultdict(lambda: [0, 0.0])
        for s in self.spans:
            if self.in_window(s):
                out[s.name][0] += 1
                out[s.name][1] += self.trace._clip(s) * 1e-9
        return {k: {"n": v[0], "s": v[1]} for k, v in sorted(out.items())}


def program_spans(pd, prefix: str = SPAN_PREFIX) -> list[Span]:
    """The host spans named ``prefix``* of a ``ProfileData``, with their
    arguments, on the clock ``tracefile`` puts everything on."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.append(Span(e.name, e.start_ns, e.duration_ns,
                                        {k: v for k, v in e.stats
                                         if k != "_r"}))
    return sorted(out, key=lambda s: s.start)


def load(path, trace: Trace | None = None, pd=None) -> ProgramTrace:
    """The program's side of the trace in ``path`` (an ``.xplane.pb``);
    ``trace`` is its reduction by ``tracefile.load`` and ``pd`` its
    ``ProfileData`` (each made here if not given)."""
    from jax.profiler import ProfileData
    path = str(path)
    if trace is None:
        trace = tracefile.load(path)
    spans = program_spans(pd if pd is not None
                          else ProfileData.from_file(path))
    scopes = []
    for d, ops in enumerate(device_op_scopes(path)):
        have = trace.ops[d]
        if len(ops) != len(have) or any(
                a.name != name for a, (name, _) in zip(have, ops)):
            raise ValueError(f"{path}: device {d}'s ops differ from the "
                             f"trace's")
        scopes.append([s for _, s in ops])
    return ProgramTrace(trace=trace, spans=spans, scopes=scopes)


def run_trace_files() -> list[str]:
    """The ``.xplane.pb`` files under ``bench/run.py``'s trace
    directories (``bench-trace-*`` in the temp directory), newest first."""
    pattern = os.path.join(tempfile.gettempdir(), "bench-trace-*", "**",
                           "*.xplane.pb")
    return sorted(glob.glob(pattern, recursive=True),
                  key=os.path.getmtime, reverse=True)


def of(trace: Trace) -> ProgramTrace | None:
    """The program's side of ``trace``: loaded once, from the run trace
    file whose ``bench.trace_window`` is the trace's window.  None where
    no file matches or it cannot be read."""
    pt = getattr(trace, "program", None)
    if pt is not None:
        return pt
    from jax.profiler import ProfileData
    for path in run_trace_files():
        try:
            pd = ProfileData.from_file(path)
            if [(w.start, w.end) for w in program_spans(pd, WINDOW_SPAN)
                ] != [trace.window]:
                continue
            return load(path, trace, pd)
        except Exception as e:    # a reader never fails the run
            print(f"bench: program trace {path}: {e}", file=sys.stderr)
            return None
    return None


def report(path) -> dict:
    pt = load(path)
    waits = list(pt.queue_waits().values())
    out = {"window_s": pt.trace.window_s, "busy_s": pt.trace.busy_s(),
           "spans": pt.span_summary(),
           "idle_gaps": [list(g) for g in pt.idle_gaps(0, 10)],
           "queue_wait_ms": {"n": len(waits), "median": (
               statistics.median(waits) if waits else None),
               "max": max(waits, default=None)}}
    if pt.trace.n_devices and pt.trace.modules_named(STEP_MODULE):
        out["train_step"] = pt.scope_breakdown()
    return out


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
