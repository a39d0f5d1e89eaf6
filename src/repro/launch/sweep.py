"""Sweep launcher — fan a base RunSpec across declarative overrides.

  # lr grid, sequential in-process members:
  PYTHONPATH=src python -m repro.launch.sweep --base spec.json \
      --dir out/sweep --grid '{"opt.lr": [1e-3, 3e-3], "seed": [0, 1]}'

  # optimizer ablation as crash-isolated subprocesses, 2 at a time,
  # each on a 2x2 virtual-device mesh:
  PYTHONPATH=src python -m repro.launch.sweep --base spec.json \
      --dir out/ablate --variants variants.json --subprocess --parallel 2 \
      --virtual-devices 4

``variants.json`` is a list of override dicts (dotted spec paths):
``[{"opt.name": "adamw", "opt.lr": 2e-4}, {"opt.lr": 1e-3}, ...]``.

Re-invoking the same command is always safe: DONE members are skipped,
killed or preempted members resume from their last complete checkpoint
(see DESIGN.md §"Elastic training fleet").  The merged, ranked report
lands in ``<dir>/report.json``.
"""
import os
import sys

from repro.launch.train import parse_virtual_devices

_n = parse_virtual_devices(sys.argv[1:]) if __name__ == "__main__" else None
if _n:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={_n}")


def _load_variants(args) -> list:
    import json
    if (args.grid is None) == (args.variants is None):
        raise SystemExit("pass exactly one of --grid / --variants")
    from repro.fleet.sweep import expand_grid
    if args.grid:
        text = args.grid
        if text.startswith("@"):
            with open(text[1:]) as f:
                text = f.read()
        return expand_grid(json.loads(text))
    with open(args.variants) as f:
        variants = json.load(f)
    if not isinstance(variants, list):
        raise SystemExit("--variants file must hold a JSON list of "
                         "override dicts")
    return variants


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--base", required=True,
                    help="base RunSpec JSON file")
    ap.add_argument("--dir", required=True,
                    help="sweep directory (members + report.json)")
    ap.add_argument("--grid", default=None,
                    help="JSON {dotted.path: [values...]} expanded as a "
                         "cartesian product (or @file.json)")
    ap.add_argument("--variants", default=None,
                    help="JSON file: explicit list of override dicts")
    ap.add_argument("--subprocess", action="store_true",
                    help="run members as crash-isolated subprocesses "
                         "(default: sequential in-process)")
    ap.add_argument("--parallel", type=int, default=1,
                    help="max subprocess members in flight")
    ap.add_argument("--objective", default="loss",
                    choices=["loss", "eval_loss"],
                    help="ranking key for the report")
    ap.add_argument("--virtual-devices", type=int, default=None,
                    help="host-platform device count (handled pre-import; "
                         "forwarded to subprocess members)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()   # for in-process members; no backend starts
    variants = _load_variants(args)
    with open(args.base) as f:
        from repro.run.spec import RunSpec
        base = RunSpec.from_json(f.read())

    extra = (["--virtual-devices", str(args.virtual_devices)]
             if args.virtual_devices else [])
    from repro.fleet.sweep import run_sweep
    report = run_sweep(base, variants, args.dir,
                       mode="subprocess" if args.subprocess else "inproc",
                       parallel=args.parallel, extra_args=extra,
                       objective=args.objective)

    done, n = report["n_done"], report["n_members"]
    print(f"\nsweep: {done}/{n} members done; report: "
          f"{os.path.join(args.dir, 'report.json')}")
    for rank, name in enumerate(report["ranking"], 1):
        row = next(r for r in report["members"] if r["name"] == name)
        print(f"  #{rank} {name}  {report['objective']}="
              f"{row[report['objective']]:.4f}  "
              f"overrides={json.dumps(row['overrides'])}")
    if done < n:
        print("  (re-invoke the same command to resume unfinished members)")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
