"""The check's readings for setting its limits, on the chip at a cell's
own size: the program's (sound runs), the control's (the reference
computed in float8, the step below the configuration's bfloat16), and
those of the faults a cell can have, planted in the reference.

  python3 bench/control.py --workload <cell> --seeds 1,2,3,4 \
      --control-seeds 1,2,3 [--seconds S]

Every seed gives the program's readings; the seeds of ``--control-seeds``
also give the control's and the faults'.

Training: the program's three set-up steps and one window step, then the
reference; the control and the half-batch fault (the reference on half
of every batch, the mean over the rest) are compared with the same
reference.  A state left unchanged reads 1 by the change-norm measure
and needs no run.  Serving: a short window at the cell's own load; the
control reads, at each served position, the reference gap of the token
float8 puts first; the fault alters one served token per request.

One JSON line per seed on stdout.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402


def half_batch(batch: dict) -> dict:
    """The first half of the rows."""
    n = batch["tokens"].shape[0] // 2
    return {k: v[:n] for k, v in batch.items()}


def train_readings(cfg, traffic, seed, res, control, devices) -> dict:
    from bench.reference import TrainReference
    from bench.train import readings
    out = {"program": res["readings"]}
    opt = traffic["optimizer"]
    if control:
        ctl = TrainReference(cfg, opt, "fp8", devices).run(
            seed, res["batches"])
        out["control"] = readings(ctl, res["ref"])
        half = TrainReference(cfg, opt, devices=devices).run(
            seed, [half_batch(b) for b in res["batches"]])
        out["half_batch"] = readings(half, res["ref"])
    return out


def serve_readings(cfg, traffic, seed, res, control, devices) -> dict:
    from bench.reference import LogitsReference
    from bench.serve import control_gap, served_gap
    out = {"program": res["readings"]}
    ref, sample = res["ref"], res["sample"]
    if control:
        ctl = LogitsReference(cfg, seed, "fp8")
        gap, n = control_gap(ref, ctl, sample)
        out["control"] = {"served_logit_gap": gap, "tokens_compared": n}
        del ctl
        V = cfg["vocab_size"]
        bad = [(p, o[:len(o) // 2] + [(o[len(o) // 2] + 1) % V]
                + o[len(o) // 2 + 1:]) for p, o in sample]
        gap, n = served_gap(ref, bad)
        out["altered_token"] = {"served_logit_gap": gap,
                                "tokens_compared": n}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.find_cell(bench, args.workload)
    try:
        devices = harness.require_tpu(int(cell["chips"]))
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    harness.enable_compile_cache()
    cfg = harness.config(cell["config"])
    traffic = harness.traffic(cell["traffic"])
    limits = harness.load_json(harness.BENCH / "limits"
                               / f"{cell['name']}.json")
    harness.check_mesh(cell, traffic)
    if traffic["kind"] == "train":
        from bench.train import run_cell
        read = train_readings
    else:
        from bench.serve import run_cell
        read = serve_readings
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        with harness.stdout_to_stderr(), \
                harness.CompileCounter() as counter:
            res = run_cell(cfg, traffic, seed, args.seconds, None, devices,
                           counter, limits)
            out = read(cfg, traffic, seed, res, seed in control, devices)
        out.update(seed=seed, seconds=time.perf_counter() - t0,
                   memory=res["memory"], footprint=res["footprint"])
        print(json.dumps(out), flush=True)
        del res
    return 0


if __name__ == "__main__":
    sys.exit(main())
