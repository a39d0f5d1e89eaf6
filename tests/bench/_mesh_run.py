"""Run by ``test_bench_mesh.py`` in a process of its own with four CPU
devices: the tiny Qwen3 training cell on one device and on a 2x2 mesh
through the program's mesh path, the planted faults on the mesh, the
weights made straight into the mesh's shardings, and the reference with
its layers spread over the four devices.  Prints one JSON line."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1], HERE.parents[1] / "src"):
    sys.path.insert(0, str(p))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from _tiny import (TINY_QWEN3, execute, half_batch, state_unchanged,  # noqa: E402
                   train_traffic)
from bench import gen, train, weights as W  # noqa: E402
from bench.reference import TrainReference  # noqa: E402

CELL = "train.danube-1.8b.seq4k"
SEED = 2 ** 35 + 3


def run(mesh, fault=None, chips=4):
    tr = dict(train_traffic(), **({"mesh": mesh} if mesh else {}))
    out = execute(CELL, tr, fault=fault, cfg=TINY_QWEN3, chips=chips,
                  seed=SEED)
    return {"correct": out["correct"], "checks": out["checks"]}


def losses_of(capture):
    """The program's set-up losses, from the run's readings line."""
    line = [ln for ln in capture.splitlines() if ln.startswith('{"setup"')]
    return json.loads(line[-1])["readings"]["losses"]


def weights_placed():
    tr = dict(train_traffic(), mesh=[2, 2])
    spec = train.run_spec(TINY_QWEN3, tr, SEED)
    arch = train.program_arch(TINY_QWEN3)
    sh = train.param_shardings(spec, arch)
    placed = W.init_params(TINY_QWEN3, SEED, sh)
    whole = W.init_params(TINY_QWEN3, SEED)
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(placed), jax.tree.leaves(whole)))
    where = all(a.sharding == s for a, s in
                zip(jax.tree.leaves(placed), jax.tree.leaves(sh)))
    return {"equal": same, "in_shardings": where,
            "sharded_leaves": sum(not s.is_fully_replicated
                                  for s in jax.tree.leaves(sh))}


def reference_spread():
    tr = train_traffic()
    g = gen.TrainTraffic(tr, TINY_QWEN3["vocab_size"], SEED)
    batches = [g.batch(s) for s in range(tr["setup_steps"])]
    one = TrainReference(TINY_QWEN3, tr["optimizer"],
                         devices=jax.devices()[:1]).run(SEED, batches)
    four = TrainReference(TINY_QWEN3, tr["optimizer"],
                          devices=jax.devices()).run(SEED, batches)
    return {"bitwise": one == four}


def main():
    import contextlib
    import io
    assert len(jax.devices()) == 4, jax.devices()
    out = {}
    for name, mesh, chips in (("one", None, 1), ("mesh", [2, 2], 4)):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            out[name] = run(mesh, chips=chips)
        out[name]["losses"] = losses_of(buf.getvalue())
    with contextlib.redirect_stdout(io.StringIO()):
        for fault in (state_unchanged, half_batch):
            out[fault.__name__] = run([2, 2], fault)
    out["weights"] = weights_placed()
    out["reference"] = reference_spread()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
