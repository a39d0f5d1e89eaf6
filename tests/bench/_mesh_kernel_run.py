"""Run by ``test_bench_mesh_kernel.py`` in a process of its own with four
CPU devices: the tiny Qwen3 training cell with the AdaLomo rule on its
Pallas kernel (interpret mode), on one device and on a 2x2 mesh through
the program's mesh path, where each device updates its own shard of
every matrix.  Prints one JSON line."""
import contextlib
import functools
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1], HERE.parents[1] / "src"):
    sys.path.insert(0, str(p))

import jax  # noqa: E402

from _tiny import TINY_QWEN3, execute, train_traffic  # noqa: E402
from repro.core import optimizers  # noqa: E402

CELL = "train.qwen3-32b.fsdp2x2"
SEED = 2 ** 35 + 5


def losses_of(capture):
    """The program's set-up losses, from the run's readings line."""
    line = [ln for ln in capture.splitlines() if ln.startswith('{"setup"')]
    return json.loads(line[-1])["readings"]["losses"]


def main():
    assert len(jax.devices()) == 4, jax.devices()
    # the rule the cell builds by name takes the kernel, interpreted
    optimizers.REGISTRY["adalomo"] = functools.partial(
        optimizers.adalomo, backend="pallas", interpret=True)
    # the specs the kernel is put on shards with, as the step is traced
    # (None: no sharding reached the rule)
    specs = []
    on_shards = optimizers._on_shards

    def recording(kernel, sharding):
        if sharding is not None:
            specs.append(str(sharding.spec))
        return on_shards(kernel, sharding)

    optimizers._on_shards = recording
    out = {}
    for name, mesh, chips in (("one", None, 1), ("mesh", [2, 2], 4)):
        tr = train_traffic("fsdp2x2")
        if mesh is None:
            del tr["mesh"]
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            res = execute(CELL, tr, cfg=TINY_QWEN3, chips=chips, seed=SEED)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "losses": losses_of(buf.getvalue()),
                     "shard_specs": sorted(set(specs)),
                     "sharded_updates": len(specs)}
        specs.clear()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
