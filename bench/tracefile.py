"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations and programs, host spans, busy time,
idle gaps named by the host span open across them.

Device events are moved onto the host clock: the device clock of a TPU
trace runs a millisecond or two behind the host's, and a program cannot
start before the host enqueued it, so the shift is the largest lag
between an enqueue (``DoEnqueueProgram``) and the start of the program
it enqueued (same ``run_id``).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
# ops that contain other ops of the same line (a scan's loop): their time
# is their children's, so they count for busy time but not as an op
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns, host clock
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def op_name(hlo: str) -> str:
    """``%name.12 = ...`` -> ``name.12``."""
    head = hlo.split(" ", 1)[0]
    return head[1:] if head.startswith("%") else head


def op_kind(hlo: str) -> str:
    """An op's name without its instance number: ``fusion.12`` ->
    ``fusion``; groups the instances of one kernel."""
    return re.sub(r"\.\d+$", "", op_name(hlo))


@dataclasses.dataclass
class Trace:
    ops: list            # per device: [Event] from "XLA Ops"
    modules: list        # per device: [Event] from "XLA Modules"
    spans: list          # [Event] host spans named bench.*
    window: tuple        # (start, end) ns of the traced window

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, e: Event) -> float:
        a, b = max(e.start, self.window[0]), min(e.end, self.window[1])
        return max(0.0, b - a)

    def busy_intervals(self, device: int) -> list[tuple[float, float]]:
        """Union of the device's op intervals inside the window."""
        w0, w1 = self.window
        iv = sorted((max(e.start, w0), min(e.end, w1))
                    for e in self.ops[device] if e.end > w0 and e.start < w1)
        out: list[list[float]] = []
        for a, b in iv:
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        """Seconds some op ran, averaged over the devices."""
        tot = sum(b - a for d in range(self.n_devices)
                  for a, b in self.busy_intervals(d))
        return tot * 1e-9 / max(self.n_devices, 1)

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern`` (a
        regex on the name without ``%``), summed over devices."""
        rx = re.compile(pattern)
        return sum(self._clip(e) for ops in self.ops for e in ops
                   if rx.match(op_name(e.name))) * 1e-9

    def modules_named(self, prefix: str, device: int = 0) -> list[Event]:
        """Whole programs (``jit_<fn>``) inside the window."""
        w0, w1 = self.window
        return [e for e in self.modules[device]
                if e.name.startswith(prefix) and e.start >= w0
                and e.end <= w1]

    def ops_in(self, module: Event, pattern: str, device: int = 0
               ) -> float:
        """Device seconds of matching ops inside one program run."""
        rx = re.compile(pattern)
        return sum(e.dur for e in self.ops[device]
                   if e.start >= module.start and e.end <= module.end
                   and rx.match(op_name(e.name))) * 1e-9

    def span_at(self, t: float) -> str:
        """The innermost bench span open at host time t."""
        best = None
        for s in self.spans:
            if s.start <= t <= s.end and s.name != WINDOW_SPAN and (
                    best is None or s.dur < best.dur):
                best = s
        return best.name if best is not None else "(no span)"

    def idle_gaps(self, device: int = 0, n: int = 10
                  ) -> list[tuple[str, float]]:
        """The n longest idle stretches inside the window, each named by
        the host span open at its middle."""
        busy = self.busy_intervals(device)
        w0, w1 = self.window
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.span_at((a + b) / 2), (b - a) * 1e-9)
                for a, b in gaps[:n]]

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        """Device seconds by op kind, largest first (device 0), loops
        left out (their children are listed)."""
        acc: dict[str, float] = collections.defaultdict(float)
        for e in self.ops[0]:
            kind = op_kind(e.name)
            if kind not in CONTAINERS:
                acc[kind] += self._clip(e)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9) for k, v in top]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops(10)],
                "idle_gaps": [list(x) for x in self.idle_gaps(0, 10)]}


def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def reduce_profile(pd, span_prefix: str = SPAN_PREFIX) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``; host spans
    are the events whose name starts with ``span_prefix``."""
    enqueued: dict[int, float] = {}
    spans: list[Event] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
                    elif e.name == "DoEnqueueProgram":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            enqueued[int(rid)] = e.start_ns
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    ops, modules = [], []
    for plane in devices:
        raw_ops, raw_mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                raw_ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
            elif line.name == "XLA Modules":
                raw_mods = [(e.name, e.start_ns, e.duration_ns,
                             _stats(e).get("run_id")) for e in line.events]
        lags = [enqueued[int(r)] - s for _, s, _, r in raw_mods
                if r is not None and int(r) in enqueued]
        shift = max(lags) if lags else 0.0
        ops.append([Event(n, s + shift, d) for n, s, d in raw_ops])
        modules.append([Event(n.split("(", 1)[0], s + shift, d)
                        for n, s, d, _ in raw_mods])
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        window = (windows[0].start, windows[0].end)
    else:
        allev = [e for o in ops + modules for e in o]
        window = (min(e.start for e in allev), max(e.end for e in allev))
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def load(trace_dir: str | Path, span_prefix: str = SPAN_PREFIX) -> Trace:
    """Reduce the one ``.xplane.pb`` the profiler wrote under trace_dir
    (or that file itself)."""
    from jax.profiler import ProfileData
    files = ([str(trace_dir)] if str(trace_dir).endswith(".xplane.pb")
             else sorted(glob.glob(str(Path(trace_dir) / "**"
                                       / "*.xplane.pb"), recursive=True)))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(files[-1]), span_prefix)
