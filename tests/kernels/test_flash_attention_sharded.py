"""The flash-attention kernels on a mesh, each device on its own kv heads,
equal the kernels on one device: four CPU devices in a process of their
own (``_sharded_flash_run.py``), the kernels in interpret mode, on a 2x2
(data, model) mesh; shared (S,) positions, packed segment ids, a window
with a padded sequence, and bfloat16.

Tolerances: against the one-device kernels, 1e-6 absolute on the output
and dq, dk, dv: a device runs the same kernels on its own heads, so only
the split of the K axis differs.  ``layers.attention`` on the mesh,
dispatched as on a TPU, against the ``jnp`` scan on one device: 1e-5 on
the output and 5e-5 on the gradients (float32; the kernels' and the
scan's blocks sum in another order, as ``test_flash_vjp`` allows); a K
that the ``model`` axis does not divide keeps the scan."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).parent / "_sharded_flash_run.py"
KERNEL_CASES = ["shared_pos", "packed", "window_pad", "bf16_packed"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_sharded_kernels_equal_the_one_device_kernels(runs, name):
    got = runs[name]
    assert max(got["one_device"].values()) <= 1e-6, got
    # the output comes back split over the kv heads, as q came in
    assert got["out_spec"] == "PartitionSpec(None, None, 'model')"


def test_attention_on_the_mesh_takes_the_sharded_kernels(runs):
    got = runs["kv_heads_over_model"]
    assert got["kernel"] and got["shard_map"], got
    assert got["scan"]["out"] <= 1e-5, got
    assert max(got["scan"][g] for g in ("dq", "dk", "dv")) <= 5e-5, got


def test_attention_keeps_the_scan_when_the_axis_does_not_divide_k(runs):
    got = runs["kv_heads_indivisible"]
    assert not got["kernel"] and not got["shard_map"], got
    assert max(got["scan"].values()) <= 1e-6, got
