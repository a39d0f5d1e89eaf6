"""Production training launcher — RunSpec parsing + ``run()`` (Run API v1).

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --smoke --steps 100 --optimizer adalomo --batch 8 --seq 128

  PYTHONPATH=src python -m repro.launch.train --spec runspec.json

On a real cluster this binary runs once per host (jax.distributed
initializes from the standard env vars); in this container it runs
single-process, optionally with a virtual-device mesh (--virtual-devices N
or --virtual-devices=N; must be handled before any jax import because the
device count locks at first jax use).
"""
import os
import sys


def parse_virtual_devices(argv) -> int | None:
    """Extract --virtual-devices from raw argv, before argparse/jax.

    Accepts both ``--virtual-devices N`` and ``--virtual-devices=N``;
    raises SystemExit with a clear message when the value is missing or
    not a positive integer (the old raw-index arithmetic crashed with an
    IndexError/ValueError on the ``=`` form or a trailing flag).
    """
    val = None
    for i, a in enumerate(argv):
        if a == "--virtual-devices":
            if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
                raise SystemExit(
                    "--virtual-devices requires a value (an integer >= 1)")
            val = argv[i + 1]
        elif a.startswith("--virtual-devices="):
            val = a.split("=", 1)[1]
        else:
            continue
        if not val.isdigit() or int(val) < 1:
            raise SystemExit(
                f"--virtual-devices: expected an integer >= 1, got {val!r}")
        return int(val)
    return None


_n = parse_virtual_devices(sys.argv[1:]) if __name__ == "__main__" else None
if _n:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={_n}")


def main(argv=None):
    import argparse
    import json

    from repro.run.spec import RunSpec, add_cli_args, from_cli_args

    ap = argparse.ArgumentParser(description=__doc__)
    add_cli_args(ap)
    ap.add_argument("--spec", default=None,
                    help="RunSpec JSON file (overrides the other flags)")
    ap.add_argument("--elastic-from", default=None, metavar="CKPT_DIR",
                    help="resume this run from an existing checkpoint dir "
                         "onto the CURRENT mesh (combine with --mesh-shape "
                         "to restore onto a different device count after "
                         "pod loss/growth)")
    ap.add_argument("--virtual-devices", type=int, default=None,
                    help="host-platform device count (handled pre-import)")
    ap.add_argument("--history-out", default=None,
                    help="write the training history JSON here")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.spec:
        with open(args.spec) as f:
            spec = RunSpec.from_json(f.read())
    else:
        spec = from_cli_args(args)

    if args.elastic_from:
        # Elastic restore: take the recorded spec as-is, point it at the
        # existing checkpoints, and (optionally) override the mesh shape
        # — CheckpointManager.restore(shardings=...) re-shards the state,
        # including AdaLomo's factored moments, onto the new mesh.
        import dataclasses

        from repro.run.spec import MeshSpec, parse_mesh_shape
        mesh = spec.mesh
        shape = parse_mesh_shape(getattr(args, "mesh_shape", None))
        if shape:
            mesh = MeshSpec(kind="multi", optimized=mesh.optimized,
                            shape=shape)
        spec = dataclasses.replace(
            spec,
            mesh=mesh,
            checkpoint=dataclasses.replace(
                spec.checkpoint, dir=args.elastic_from, resume=True,
                gc_incomplete=True))

    if args.virtual_devices:
        # The XLA flag only takes effect when set before jax initializes —
        # the module-level pre-parse does that for CLI invocations.  Catch
        # programmatic main() calls where it can no longer apply.
        import jax
        if jax.device_count() < args.virtual_devices:
            raise SystemExit(
                f"--virtual-devices={args.virtual_devices} had no effect "
                f"({jax.device_count()} device(s) visible): the flag must "
                "be processed before jax initializes — invoke via "
                "`python -m repro.launch.train` on the command line")

    from repro.fleet.preempt import PREEMPTED_EXIT_CODE, Preempted
    from repro.run import run

    try:
        result = run(spec)
    except Preempted as e:
        # Resumable by re-invoking with --resume / --elastic-from (the
        # sweep driver keys on this exit code).
        print(f"preempted: checkpointed at step {e.step}; exiting "
              f"{PREEMPTED_EXIT_CODE} (resumable)")
        raise SystemExit(PREEMPTED_EXIT_CODE)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(result.history, f)
    if result.history.get("loss"):
        print(f"final loss {result.history['loss'][-1]:.4f}")
    else:
        # --resume found the run already at total_steps: a no-op resume
        print(f"nothing to do: resumed at step {result.start_step} of "
              f"{spec.steps.total}")


if __name__ == "__main__":
    main()
