"""The command exits non-zero and prints no result without a TPU, and
reads BENCHMARK.json and the files each cell names."""
import json
import os
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.danube-1.8b.seq4k", "--seed", str(2 ** 33), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_every_cell_finds_its_files():
    bench = harness.benchmark()
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["config"] in names
        cfg = harness.config(cell["config"])
        assert cfg["name"] == cell["config"]
        tr = harness.traffic(cell["traffic"])
        assert tr["kind"] in ("train", "serve")
        limits = harness.load_json(harness.BENCH / "limits"
                                   / f"{cell['name']}.json")
        assert limits and all(v > 0 for v in limits.values())
        assert harness.cell_metrics(bench, cell, "per_layer")
        e2e = {m["name"] for m in harness.cell_metrics(bench, cell,
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
        moves = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert moves
        for w in m.get("workloads", []):
            assert w in moves[0].get("workloads", [w])


def test_result_line_puts_checks_last():
    line = harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.0, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        checks={"loss_gap": {"value": 0.1, "limit": 0.2}})
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(out)
