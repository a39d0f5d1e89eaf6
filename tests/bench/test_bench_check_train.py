"""A whole training-cell run on the CPU at a tiny size, past the chip
check, for a danube-shaped and a Qwen3-shaped (qk-norm) configuration:
sound, it is correct under the cell's limits; with the timed step broken
underneath (state returned unchanged, half of every batch left out),
``correct`` comes out false.  A traffic mesh that is not the cell's chips
is refused before set-up; a configuration cut in depth runs at its own
depth."""
import pytest

from _tiny import (CONFIGS, TINY_QWEN3, execute, half_batch, state_unchanged,
                   train_traffic)
from bench import train, weights as W

CELL = "train.danube-1.8b.seq4k"


@pytest.mark.parametrize("cfg", CONFIGS.values(), ids=CONFIGS)
def test_sound_run_is_correct(cfg):
    out = execute(CELL, train_traffic(), cfg=cfg)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s",
                                   "peak_hbm_gib"}
    assert list(out["checks"]) == ["loss_gap", "grad_norm_gap",
                                   "change_norm_gap"]


@pytest.mark.parametrize("cfg", CONFIGS.values(), ids=CONFIGS)
@pytest.mark.parametrize("fault,fails", [
    (state_unchanged, "change_norm_gap"),
    (half_batch, "grad_norm_gap"),
])
def test_broken_step_is_not_correct(fault, fails, cfg):
    out = execute(CELL, train_traffic(), fault=fault, cfg=cfg)
    assert not out["correct"]
    c = out["checks"][fails]
    assert c["value"] > c["limit"]


def test_mesh_not_the_cells_chips_is_refused_before_setup(monkeypatch):
    def no_setup(*a, **k):
        raise AssertionError("set-up started")

    monkeypatch.setattr(W, "init_params", no_setup)
    for mesh in ([2, 2], [2]):
        with pytest.raises(SystemExit, match="chips"):
            execute(CELL, dict(train_traffic(), mesh=mesh),
                    cfg=TINY_QWEN3)
    with pytest.raises(SystemExit, match="chips"):
        execute(CELL, train_traffic(), cfg=TINY_QWEN3, chips=4)


DEPTH_CUT = dict(TINY_QWEN3, num_hidden_layers=1,
                 reduced=["num_hidden_layers"],
                 published={"num_hidden_layers": 2})


def test_depth_cut_in_the_file_runs_at_its_own_depth():
    assert train.program_arch(DEPTH_CUT).cfg.n_layers == 1
    out = execute(CELL, train_traffic(), cfg=DEPTH_CUT)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cfg", [
    dict(DEPTH_CUT, published={"num_hidden_layers": 64}),  # not the program's
    dict(TINY_QWEN3, num_hidden_layers=1),                  # not declared
    dict(TINY_QWEN3, qk_norm=False),
])
def test_configuration_the_program_does_not_build_is_refused(cfg):
    with pytest.raises(SystemExit, match="qwen3-32b"):
        train.program_arch(cfg)
