"""Sweep a serving cell's arrival rate on the chip, in one process on one
engine, to find its knee: the highest rate the engine sustains with no
growing backlog.  The cell's entry fixes its rate from this sweep once;
the benchmark's runs never run it.

  python3 bench/knee.py --workload serve.danube-1.8b.chat \
      --rates 1.5,2,2.5,3,3.5 --seconds 30 --seed 1

One JSON line per rate: requests due and finished in the window, the
queue at its close, TTFT and TPOT percentiles.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import gen, harness  # noqa: E402
from bench.harness import log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.benchmark(), args.workload)
    try:
        harness.require_tpu(int(cell["chips"]))
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    harness.enable_compile_cache()
    from bench import serve
    cfg = harness.config(cell["config"])
    traffic = harness.traffic(cell["traffic"])
    V = cfg["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    # warm up the prefill buckets of the most requests any rate sends
    arrivals = gen.serve_arrivals(dict(traffic, rate=max(rates)), V,
                                  args.seed, args.seconds)
    with harness.stdout_to_stderr():
        engine = serve.build_engine(cfg, traffic, args.seed, arrivals)
    for rate in rates:
        t = dict(traffic, rate=rate)
        arr = gen.serve_arrivals(t, V, args.seed, args.seconds)
        win = serve.Window(engine, arr, float(t["lead_in_s"]),
                           args.seconds).run()
        st = {k: v for k, v in win.stats().items()
              if not isinstance(v, list)}
        st["rate"] = rate
        st["finished_per_s"] = st["finished_in_window"] / args.seconds
        print(json.dumps(st), flush=True)
        while engine.scheduler.has_work():     # drain before the next
            engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
