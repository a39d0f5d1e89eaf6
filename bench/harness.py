"""What every cell shares: finding a cell's files by name, the chip check,
the compile cache, compile counting, host spans, seeds and the result line.

Nothing here imports the program; cells import it once the chip is found.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"
GIB = 2 ** 30


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no entry for device kind "
                       f"{device_kind!r}")
    return table[device_kind]


def metric_reader(name: str):
    """The ``read(trace, record)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: dict, key: str) -> list[dict]:
    """The end-to-end (``key="end_to_end"``) or per-layer metrics this cell
    reports: those without a ``workloads`` list, and those that name it."""
    return [m for m in bench[key]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def check_mesh(cell: dict, traffic: dict) -> None:
    """A traffic mix's ``mesh`` (the program's ``MeshSpec.shape``) must
    hold the cell's chips, no more and no fewer; without one the cell
    runs on one chip."""
    shape = traffic.get("mesh", [])
    if math.prod(shape) != int(cell["chips"]):
        raise SystemExit(f"bench: {cell['name']} asks for {cell['chips']} "
                         f"chips; its traffic {cell['traffic']!r} runs on "
                         f"a mesh of {shape or [1]}")


def require_tpu(n_chips: int):
    """The cell's devices; raises NoChip without a TPU or with too few."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU found: JAX could not start a backend ({e})")
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX runs on {devices[0].platform!r}")
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:n_chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache``, every
    program cached, source paths made checkout-relative (a Pallas kernel
    carries its source locations into the cache key).  Eviction stays off,
    whatever the environment sets: with it on, one entry left without its
    access-time file (a run killed mid-write, or a cache written with it
    off) makes every later write fail, and every run compiles again."""
    import jax
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}{os.sep}"))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts programs compiled or loaded from the compile cache, from
    JAX's monitoring events, while the ``with`` block runs."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        self.count = 0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        if name in self.EVENTS:
            self.count += 1


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class OpenSpan:
    """A span opened in one call and closed in another."""

    def __init__(self):
        self._cm = None

    def open(self, name: str):
        self.close()
        self._cm = span(name)
        self._cm.__enter__()

    def close(self):
        if self._cm is not None:
            self._cm.__exit__(None, None, None)
            self._cm = None


def prng_key(seed: int):
    """A JAX key from any non-negative whole number, 64 bits and more."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def memory(devices) -> dict:
    """bytes_in_use and peak_bytes_in_use of the fullest chip."""
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return {"bytes_in_use": max(s.get("bytes_in_use", 0) for s in stats),
            "peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats),
            "bytes_limit": min(s.get("bytes_limit", 0) for s in stats),
            "stats": fullest}


def program_extra_bytes(compiled) -> int:
    """Device bytes a compiled program adds while it runs, beyond the
    buffers already resident: its temporaries and the outputs that do not
    reuse a donated argument, by XLA's accounting of the program."""
    ma = compiled.memory_analysis()
    return int(ma.temp_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes)


def footprint(mem: dict, resident: int, programs: dict) -> dict:
    """The cell's device memory: the larger of the allocator's peak and
    the resident buffers plus the timed program that adds the most.  The
    allocator's peak leaves out what a program allocates for itself on the
    TPU, so it alone would not see activations.  ``programs`` maps a name
    to a compiled program of the timed path."""
    extra = {name: program_extra_bytes(c) for name, c in programs.items()}
    return {"bytes": max(int(mem["peak_bytes_in_use"]),
                         int(resident) + max(extra.values(), default=0)),
            "resident": int(resident), "program_extra": extra}


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def stdout_to_stderr():
    """Program output (the run loop's log lines) goes to stderr, so the
    result stays the last line of stdout."""
    old = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield
    finally:
        sys.stdout = old


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
