"""The cross-chip collectives of the training step, from a device trace:
each chip's collective ops inside the ``jit_one_step`` runs, by kind, and
the time they run with no other op of that chip beside them.

An op is told by its HLO text (the name of a trace event of the "XLA
Ops" line): its name or, for a fusion, the computation it calls
(``all-reduce-scatter``).  Kinds: ``all-gather``, ``all-reduce``,
``reduce-scatter`` and ``other`` (``collective-permute``,
``all-to-all``, the ``async-collective`` pair).  An asynchronous
collective runs from its ``-start`` op to its ``-done`` op, paired on
the chip in the order they start, and counts once.  The fusions in which XLA
runs a collective inside compute (``async_collective_fusion``) count as
compute: what they carry overlaps by construction.

    python3 bench/collectives.py <trace.xplane.pb>

prints, per chip, the collectives of a step by kind and the exposed
share.
"""
from __future__ import annotations

import collections
import json
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import tracefile  # noqa: E402
from bench.tracefile import CONTAINERS  # noqa: E402

STEP_MODULE = "jit_one_step"
KINDS = ("all-gather", "all-reduce", "reduce-scatter", "other")
_KIND = [(re.compile(r"(all-reduce-scatter|reduce-scatter)"),
          "reduce-scatter"),
         (re.compile(r"all-gather"), "all-gather"),
         (re.compile(r"all-reduce"), "all-reduce"),
         (re.compile(r"(collective-permute|ragged-all-to-all|all-to-all|"
                     r"collective-broadcast|async-collective)"), "other")]
_ASYNC = re.compile(r"(.*)-(start|done)(\.\d+)?$")


def kind(text: str) -> str | None:
    """The collective kind of an op from its HLO text; None for any other
    op."""
    tags = [tracefile.op_name(text)]
    called = re.search(r"\bcalls=%([\w.-]+)", text)
    if called and tags[0].startswith("fusion"):
        tags.append(called.group(1))
    for tag in tags:
        for rx, k in _KIND:
            if rx.match(tag):
                return k
    return None


def _union(iv) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _overlap(x, y) -> float:
    """Length of the intersection of two unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        total += max(0.0, b - a)
        if x[i][1] <= y[j][1]:
            i += 1
        else:
            j += 1
    return total


def step_collectives(trace, device: int, module: str = STEP_MODULE):
    """(counts by kind, collective intervals, other-op intervals, runs) of
    the ops of one chip inside the runs of ``module`` whole in the
    window."""
    mods = trace.modules_named(module, device)
    counts = collections.Counter({k: 0 for k in KINDS})
    coll, other, pending = [], [], collections.defaultdict(list)
    for e in sorted(trace.ops[device], key=lambda e: e.start):
        if not any(m.start <= e.start and e.end <= m.end for m in mods):
            continue
        k = kind(e.name)
        name = tracefile.op_name(e.name)
        if k is None:
            if tracefile.op_kind(e.name) not in CONTAINERS:
                other.append((e.start, e.end))
            continue
        pair = _ASYNC.match(name)
        if pair is None:
            counts[k] += 1
            coll.append((e.start, e.end))
        elif pair.group(2) == "start":
            counts[k] += 1
            pending[pair.group(1)].append(e.start)
        elif pending[pair.group(1)]:
            coll.append((pending[pair.group(1)].pop(0), e.end))
        else:
            coll.append((e.start, e.end))
    return counts, coll, other, mods


def per_chip(trace, module: str = STEP_MODULE) -> list[dict]:
    """Per chip: collectives per step by kind, and the share of its busy
    time in the step (ops and collectives) in which a collective runs and
    no other op does."""
    out = []
    for d in range(trace.n_devices):
        counts, coll, other, mods = step_collectives(trace, d, module)
        if not mods:
            continue
        coll_u = _union(coll)
        busy = _length(_union(coll + other))
        exposed = _length(coll_u) - _overlap(coll_u, _union(other))
        out.append({"device": d, "steps": len(mods),
                    "per_step": {k: counts[k] / len(mods) for k in KINDS},
                    "collective_s": _length(coll_u) * 1e-9,
                    "exposed_s": exposed * 1e-9, "busy_s": busy * 1e-9,
                    "exposed_share": (100.0 * exposed / busy if busy > 0
                                      else None)})
    return out


if __name__ == "__main__":
    print(json.dumps(per_chip(tracefile.load(sys.argv[1],
                                             span_prefix="repro.")),
                     indent=1))
