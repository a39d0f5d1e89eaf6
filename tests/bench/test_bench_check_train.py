"""A whole training-cell run on the CPU at a tiny size, past the chip
check: sound, it is correct under the cell's limits; with the timed step
broken underneath (state returned unchanged, half of every batch left
out), ``correct`` comes out false."""
import jax
import pytest

from _tiny import execute, train_traffic

CELL = "train.danube-1.8b.seq4k"


def _half(batch):
    n = batch["tokens"].shape[0] // 2
    return {k: v[:n] for k, v in batch.items()}


def state_unchanged(program):
    fn = program.fn
    program.step = jax.jit(lambda p, s, b, hp: (p, s) + fn(p, s, b, hp)[2:])


def half_batch(program):
    fn = program.fn
    program.step = jax.jit(lambda p, s, b, hp: fn(p, s, _half(b), hp))


def test_sound_run_is_correct():
    out = execute(CELL, train_traffic())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s",
                                   "peak_hbm_gib"}
    assert list(out["checks"]) == ["loss_gap", "grad_norm_gap",
                                   "change_norm_gap"]


@pytest.mark.parametrize("fault,fails", [
    (state_unchanged, "change_norm_gap"),
    (half_batch, "grad_norm_gap"),
])
def test_broken_step_is_not_correct(fault, fails):
    out = execute(CELL, train_traffic(), fault=fault)
    assert not out["correct"]
    c = out["checks"][fails]
    assert c["value"] > c["limit"]
