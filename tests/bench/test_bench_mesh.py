"""The training cell on the program's mesh path, on four CPU devices in a
process of its own (``_mesh_run.py``): sound it is correct and its three
losses agree with the one-device run's inside ``loss_gap``'s limit; the
planted faults fail it; its weights are made straight into the program's
parameter shardings and equal the one-device ones; the reference with its
layers spread over the devices reads what it reads on one."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

HERE = Path(__file__).resolve().parent
LIMITS = harness.load_json(harness.BENCH / "limits"
                           / "train.danube-1.8b.seq4k.json")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "_mesh_run.py")],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_on_a_2x2_mesh_is_correct(runs):
    assert runs["one"]["correct"], runs["one"]["checks"]
    assert runs["mesh"]["correct"], runs["mesh"]["checks"]


def test_mesh_losses_agree_with_one_device(runs):
    one, mesh = runs["one"]["losses"], runs["mesh"]["losses"]
    assert len(one) == len(mesh) == 3
    assert max(abs(a - b) for a, b in zip(one, mesh)) <= LIMITS["loss_gap"]


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", "change_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_broken_step_on_the_mesh_is_not_correct(runs, fault, fails):
    assert not runs[fault]["correct"]
    c = runs[fault]["checks"][fails]
    assert c["value"] > c["limit"]


def test_weights_are_made_into_the_programs_shardings(runs):
    w = runs["weights"]
    assert w["equal"] and w["in_shardings"] and w["sharded_leaves"] > 0


def test_reference_spread_over_devices_reads_as_on_one(runs):
    assert runs["reference"]["bitwise"]
