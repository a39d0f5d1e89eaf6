"""The Qwen3 training cell on the program's mesh path with the AdaLomo
rule on its Pallas kernel (interpret mode), on four CPU devices in a
process of its own (``_mesh_kernel_run.py``): every matrix is updated on
each device's shard, the run is correct under the cell's limits, and its
three losses agree with the one-device run's inside ``loss_gap``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

HERE = Path(__file__).resolve().parent
LIMITS = harness.load_json(harness.BENCH / "limits"
                           / "train.qwen3-32b.fsdp2x2.json")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "_mesh_kernel_run.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("where", ["one", "mesh"])
def test_kernel_run_is_correct(runs, where):
    assert runs[where]["correct"], runs[where]["checks"]


def test_mesh_losses_agree_with_one_device(runs):
    one, mesh = runs["one"]["losses"], runs["mesh"]["losses"]
    assert len(one) == len(mesh) == 3
    assert max(abs(a - b) for a, b in zip(one, mesh)) <= LIMITS["loss_gap"]


def test_every_matrix_is_updated_on_shards(runs):
    # one device: the kernel on whole tensors; the mesh: in each trace of
    # the step, the block's 7 matrices and the 2 outer ones on each
    # device's shard, under the partition rules' specs
    assert runs["one"]["sharded_updates"] == 0
    n = runs["mesh"]["sharded_updates"]
    assert n > 0 and n % 9 == 0
    assert runs["mesh"]["shard_specs"] == [
        "PartitionSpec('data', 'model')", "PartitionSpec('model', 'data')"]
