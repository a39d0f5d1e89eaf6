"""The AdaLomo kernel on each device's shard of a tensor equals the
whole-tensor update: four CPU devices in a process of their own
(``_sharded_update_run.py``), the kernel in interpret mode, every spec
the partition rules give a 2-D leaf on a 2x2 mesh, and a leaf of stacked
experts, with the experts' spec and with no sharding at all.

Tolerances: θ' to 2e-6 absolute (θ ~ 0.1, steps of lr = 1e-3, float32:
the kernel's blockwise and cross-shard sums change only the rounding of
Σu² and Σθ²); r and c to 3e-5 relative, the gap the one-device kernel
already has to ref.py (β's 1 - β taken in float32 against the oracle's
Python float).  Against the one-device kernel, r and c to 1e-6: only the
order of the row and column sums differs."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).parent / "_sharded_update_run.py"
# the parameter's spec, then where r (its rows) and c (its columns) lie
SPECS = {"fsdp_tp": ("PartitionSpec('data', 'model')",
                     "PartitionSpec('data',)", "PartitionSpec('model',)"),
         "tp_fsdp": ("PartitionSpec('model', 'data')",
                     "PartitionSpec('model',)", "PartitionSpec('data',)"),
         "fsdp": ("PartitionSpec('data', None)", "PartitionSpec('data',)",
                  "PartitionSpec()"),
         "replicated": ("PartitionSpec()", "PartitionSpec()",
                        "PartitionSpec()")}
CASES = [f"{s}-{shape}-{wd}" for s in SPECS for shape in ("blocks", "ragged")
         for wd in ("no_decay", "decay")]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CASES)
def test_sharded_update_equals_the_whole_tensor_update(runs, name):
    got = runs[name]
    for whole in ("ref", "update_tensor"):
        assert got[whole]["param"] <= 2e-6, (whole, got)
        assert got[whole]["r"] <= 3e-5 and got[whole]["c"] <= 3e-5, (
            whole, got)
    one = got["one_device"]
    assert one["param"] <= 2e-6 and one["r"] <= 1e-6 and one["c"] <= 1e-6
    # each result stays where its parameter lies: θ' on θ's spec, r with
    # the rows, c with the columns
    assert (got["param_spec"], got["r_spec"], got["c_spec"]) == SPECS[
        name.split("-")[0]]


# a [4, 128, 256] leaf of experts: the experts' spec, and no sharding
# (the mesh in context runs the kernel whole on every device)
STACKED = {"experts": ("PartitionSpec('model', 'data', None)",
                       "PartitionSpec('model', 'data')",
                       "PartitionSpec('model',)"),
           "no_sharding": None}


@pytest.mark.parametrize("name", list(STACKED))
def test_stacked_update_equals_the_update_of_each_tensor(runs, name):
    got = runs[name]
    assert got["ref"]["param"] <= 2e-6, got
    assert got["ref"]["r"] <= 3e-5 and got["ref"]["c"] <= 3e-5, got
    if STACKED[name] is not None:
        assert (got["param_spec"], got["r_spec"], got["c_spec"]) == STACKED[
            name]
