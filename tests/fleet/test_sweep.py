"""Sweep driver: declarative overrides, crash-isolated members, idempotent
re-invocation, one merged ranked report."""
import dataclasses
import json

import pytest

from _fleet_common import fleet_spec
from repro.fleet import (KillAtHook, SimulatedKill, apply_overrides,
                         expand_grid, materialize, member_name, run_sweep)
from repro.fleet.sweep import member_slots
from repro.run import RunSpec

VARIANTS = [{"opt.lr": 1e-3}, {"opt.lr": 3e-3},
            {"opt.name": "adamw", "opt.lr": 2e-4}]


# ---------------------------------------------------------------------
# Declarative overrides (pure)
# ---------------------------------------------------------------------

def test_expand_grid_deterministic_product():
    got = expand_grid({"opt.lr": [1e-3, 3e-3], "seed": [0, 1]})
    assert got == [{"opt.lr": 1e-3, "seed": 0}, {"opt.lr": 1e-3, "seed": 1},
                   {"opt.lr": 3e-3, "seed": 0}, {"opt.lr": 3e-3, "seed": 1}]


def test_apply_overrides_nested_and_pure():
    base = fleet_spec()
    out = apply_overrides(base, {"opt.lr": 9e-4, "steps.total": 11,
                                 "seed": 7})
    assert (out.opt.lr, out.steps.total, out.seed) == (9e-4, 11, 7)
    # the base spec is frozen and untouched
    assert (base.opt.lr, base.steps.total) == (1e-3, 6)
    # round-trips: an overridden spec is still a plain RunSpec
    assert RunSpec.from_json(out.to_json()) == out


def test_apply_overrides_unknown_field_fails_loudly():
    with pytest.raises(ValueError, match="opt.bogus"):
        apply_overrides(fleet_spec(), {"opt.bogus": 1})
    with pytest.raises(ValueError, match="not a spec node"):
        apply_overrides(fleet_spec(), {"seed.deeper": 1})


def test_member_name_stable_and_safe():
    assert member_name(0, {"opt.lr": 0.001}) == "00_opt.lr=0.001"
    assert member_name(3, {}) == "03_base"
    weird = member_name(1, {"model/arch": "a b"})
    assert "/" not in weird and " " not in weird


@pytest.mark.parametrize("platforms,parallel,want", [
    ("cpu", 3, 3),       # host-only members share the machine
    (None, 3, 1),        # members may reach an accelerator: one at a time
    ("tpu", 2, 1),
    ("cpu,tpu", 2, 1),
    (None, 1, 1),
])
def test_member_slots_one_process_per_accelerator(monkeypatch, platforms,
                                                  parallel, want):
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    logs = []
    assert member_slots(parallel, logs.append) == want
    assert bool(logs) == (want < parallel)


def test_materialize_forces_resumable_members(tmp_path):
    members = materialize(fleet_spec(), VARIANTS, tmp_path)
    assert [m.name for m in members] == [
        "00_opt.lr=0.001", "01_opt.lr=0.003",
        "02_opt.lr=0.0002-opt.name=adamw"]
    for m in members:
        ck = m.spec.checkpoint
        assert ck.resume and ck.gc_incomplete and ck.every
        assert str(m.dir) in ck.dir
        assert m.spec.metrics_path == str(m.dir / "metrics.jsonl")
        # spec.json replays to the exact member spec
        replay = RunSpec.from_json((m.dir / "spec.json").read_text())
        assert replay == m.spec


# ---------------------------------------------------------------------
# Execution + report
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_inproc_sweep_report_and_idempotence(tmp_path):
    base = fleet_spec(total=4, every=2)
    report = run_sweep(base, VARIANTS, tmp_path / "sw",
                       log_fn=lambda s: None)

    assert report["n_members"] == 3 and report["n_done"] == 3
    assert report["objective"] == "final_loss"
    rows = {r["name"]: r for r in report["members"]}
    assert set(report["ranking"]) == set(rows)
    # ranking ascending by final loss
    losses = [rows[n]["final_loss"] for n in report["ranking"]]
    assert losses == sorted(losses)
    assert report["best"]["name"] == report["ranking"][0]
    for r in rows.values():
        assert r["status"] == "done"
        assert r["steps_done"] == 4
        assert "final_loss" in r and "best_loss" in r
    # the report is a committed-artifact-shaped JSON on disk
    on_disk = json.loads((tmp_path / "sw" / "report.json").read_text())
    assert on_disk["ranking"] == report["ranking"]
    assert on_disk["base_spec"] == base.to_dict()

    # re-invocation skips everything (DONE markers), same report
    logs = []
    report2 = run_sweep(base, VARIANTS, tmp_path / "sw", log_fn=logs.append)
    assert report2["ranking"] == report["ranking"]
    assert sum("skipping" in l for l in logs) == 3


@pytest.mark.slow
def test_crash_mid_sweep_resumes_only_unfinished(tmp_path):
    """Satellite acceptance: kill a member mid-run, re-invoke the sweep —
    finished members are skipped, the killed one resumes from its last
    complete checkpoint (not from scratch)."""
    # checkpoint.every=2 (no dir: materialize assigns per-member dirs),
    # so the kill at boundary 3 leaves the step-2 save as the newest
    from repro.run import CheckpointSpec
    base = fleet_spec(total=6, checkpoint=CheckpointSpec(every=2))
    sweep_dir = tmp_path / "sw"

    def kill_member_1(member):
        # member 01 dies at step boundary 3 (after its step-2 checkpoint)
        return (KillAtHook(3),) if member.name.startswith("01_") else ()

    # SimulatedKill is a BaseException: it takes down the whole sweep
    # driver, exactly like a process death mid-sweep
    with pytest.raises(SimulatedKill):
        run_sweep(base, VARIANTS, sweep_dir, member_hooks=kill_member_1,
                  log_fn=lambda s: None)

    names = ["00_opt.lr=0.001", "01_opt.lr=0.003",
             "02_opt.lr=0.0002-opt.name=adamw"]
    assert (sweep_dir / names[0] / "DONE.json").exists()
    assert not (sweep_dir / names[1] / "DONE.json").exists()
    assert not (sweep_dir / names[2] / "DONE.json").exists()
    # the killed member left a resumable checkpoint behind
    from repro.checkpoint.manager import CheckpointManager
    assert CheckpointManager(sweep_dir / names[1] / "ckpt").latest_step() == 2

    logs = []
    report = run_sweep(base, VARIANTS, sweep_dir, log_fn=logs.append)
    assert report["n_done"] == 3
    assert sum("skipping" in l for l in logs) == 1          # member 00 only
    assert any("resumed from step 2" in l for l in logs)    # member 01
    # the resumed member's merged metrics stream covers the full curve
    recs = [json.loads(l)
            for l in (sweep_dir / names[1] / "metrics.jsonl").open()
            if l.strip()]
    data = [r for r in recs if "schema" not in r and "event" not in r]
    assert [r["step"] for r in data] == list(range(6))
    # and its history is complete
    hist = json.loads((sweep_dir / names[1] / "history.json").read_text())
    assert len(hist["loss"]) == 6 - 2   # resumed tail


def test_failed_member_is_contained(tmp_path):
    # a member whose spec cannot build fails alone; the sweep finishes
    base = fleet_spec(total=2, every=1)
    report = run_sweep(base, [{"opt.lr": 1e-3},
                              {"opt.name": "no-such-optimizer"}],
                       tmp_path / "sw", log_fn=lambda s: None)
    rows = {r["name"]: r for r in report["members"]}
    statuses = sorted(r["status"] for r in rows.values())
    assert statuses == ["done", "failed"]
    failed = next(r for r in rows.values() if r["status"] == "failed")
    assert (tmp_path / "sw" / failed["name"] / "error.txt").exists()
    assert failed["name"] not in report["ranking"]
