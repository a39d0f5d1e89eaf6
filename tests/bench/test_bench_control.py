"""The control: the plain reference computed in float8 (the step below the
configurations' bfloat16), put in the program's place, comes out not
correct under each cell's own limits, at a size a test run holds (the
training control for a danube- and a Qwen3-shaped block).  On the chip
the same readings are taken at the cells' sizes by ``bench/control.py``."""
import numpy as np
import pytest

from _tiny import CONFIGS, TINY, train_traffic
from bench import gen, harness
from bench.reference import LogitsReference, TrainReference
from bench.serve import control_gap, served_gap
from bench.train import checks_from, readings

CFG = dict(TINY, torch_dtype="bfloat16", vocab_size=1024,
           sliding_window=None)
SEED = 2 ** 33 + 7


def limits(cell: str) -> dict:
    return harness.load_json(harness.BENCH / "limits" / f"{cell}.json")


@pytest.fixture(scope="module", params=CONFIGS, ids=CONFIGS)
def train_runs(request):
    cfg = dict(CONFIGS[request.param], torch_dtype="bfloat16",
               vocab_size=1024, sliding_window=None)
    tr = dict(train_traffic(), seq_len=64)
    g = gen.TrainTraffic(tr, cfg["vocab_size"], SEED)
    batches = [g.batch(s) for s in range(tr["setup_steps"])]
    ref = TrainReference(cfg, tr["optimizer"]).run(SEED, batches)
    ctl = TrainReference(cfg, tr["optimizer"], "fp8").run(SEED, batches)
    return ref, ctl


def test_reference_against_itself_reads_nought(train_runs):
    ref, _ = train_runs
    r = readings(ref, ref)
    assert r["loss_gap"] == r["grad_norm_gap"] == r["change_norm_gap"] == 0


def test_train_control_is_not_correct(train_runs):
    ref, ctl = train_runs
    ok, checks = checks_from(readings(ctl, ref),
                             limits("train.danube-1.8b.seq4k"))
    assert not ok, checks


def test_serve_control_is_not_correct():
    ref = LogitsReference(CFG, SEED)
    ctl = LogitsReference(CFG, SEED, "fp8")
    rng = np.random.default_rng(SEED)
    sample = []
    for _ in range(4):
        prompt, out = rng.integers(0, CFG["vocab_size"], 20).tolist(), []
        for _ in range(8):
            out.append(int(ref.logits(prompt + out)[-1].argmax()))
        sample.append((prompt, out))
    assert served_gap(ref, sample)[0] == 0.0
    gap, n = control_gap(ref, ctl, sample)
    assert n == 32
    assert gap > limits("serve.danube-1.8b.chat")["served_logit_gap"]
