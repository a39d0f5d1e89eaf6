"""Run one benchmark cell on the chip this process is started on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); the limits of its correctness check
are in ``bench/limits/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of a window cut to the mix's ``trace_seconds``.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.  The last line of stdout is the result; the
numbers compared for ``correct`` are also the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.harness import GIB, log  # noqa: E402


def cell_module(kind: str):
    if kind == "train":
        from bench import train
        return train
    if kind == "serve":
        from bench import serve
        return serve
    raise SystemExit(f"bench: unknown traffic kind {kind!r}")


def end_to_end(res: dict, setup_s: float) -> dict:
    return {"setup_s": setup_s,
            "train_tokens_per_s": res.get("tokens_per_s"),
            "ttft_p90_ms": res.get("ttft_p90_ms"),
            "tpot_p90_ms": res.get("tpot_p90_ms"),
            "peak_hbm_gib": res["footprint"]["bytes"] / GIB}


def execute(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
            devices, *, cfg=None, traffic=None, limits=None, fault=None,
            t_start=None) -> dict:
    """Everything after the chip check; returns the result object.
    ``cfg``/``traffic``/``limits`` default to the cell's files; ``fault``
    breaks the program underneath (tests of the check)."""
    t_start = T_START if t_start is None else t_start
    cfg = cfg or harness.config(cell["config"])
    traffic = traffic or harness.traffic(cell["traffic"])
    limits = limits or harness.load_json(
        harness.BENCH / "limits" / f"{cell['name']}.json")
    harness.check_mesh(cell, traffic)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with harness.CompileCounter() as counter:
            res = cell_module(traffic["kind"]).run_cell(
                cfg, traffic, seed, seconds, trace_dir, devices, counter,
                limits, fault)
        setup_s = res["setup_record"].pop("window_open") - t_start
        breakdown, device = None, harness.device_info(devices)
        device["memory_peak_bytes"] = res["footprint"]["bytes"]
        if trace:
            from bench import tracefile
            tr = tracefile.load(trace_dir)
            pk = harness.peaks(devices[0].device_kind)
            rec = dict(res["record"], peaks=pk)
            metrics = {}
            for m in harness.cell_metrics(bench, cell, "per_layer"):
                v = harness.metric_reader(m["name"])(tr, rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            breakdown = tr.breakdown()
        else:
            values = end_to_end(res, setup_s)
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in harness.cell_metrics(bench, cell,
                                                     "end_to_end")}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"setup": dict(
        res["setup_record"], setup_s=setup_s,
        compiles_in_window=res["compiles_in_window"],
        peak_bytes_in_use_gib=res["memory"]["peak_bytes_in_use"] / GIB,
        bytes_in_use_after_window_gib=res["memory"]["bytes_in_use"] / GIB,
        footprint=res["footprint"],
        memory_stats_after_window=res["memory"]["stats"]),
        "readings": res["readings"],
        **({"window": res["window_stats"]} if "window_stats" in res
           else {})}), flush=True)
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return {"correct": res["correct"] and res["compiles_in_window"] == 0,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device, "checks": res["checks"],
            "breakdown": breakdown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.find_cell(bench, args.workload)
    try:
        devices = harness.require_tpu(int(cell["chips"]))
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    log(f"bench: {cell['name']} on {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {harness.enable_compile_cache()}")
    out = execute(bench, cell, args.seed, args.seconds, bool(args.trace),
                  devices)
    print(harness.result_line(**out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
