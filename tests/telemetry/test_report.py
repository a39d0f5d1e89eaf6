"""``repro.telemetry.report``: bitwise reproduction of recorded stream
values, golden-stable text rendering, and the CLI surface (JSON
output)."""
import json
from pathlib import Path

from repro.telemetry import read_stream
from repro.telemetry.report import main, render_text, summarize

GOLDEN = Path(__file__).parent / "golden"


def _streams():
    return [read_stream(GOLDEN / n)
            for n in ("train.jsonl", "serve.jsonl", "kernel.jsonl")]


def test_summary_values_are_verbatim_stream_values():
    """The acceptance contract: loss / tokens-per-s / pool-utilization in
    the report are the recorded values BITWISE — no re-derivation."""
    train = json.loads((GOLDEN / "train.jsonl").read_text().splitlines()[-1])
    serve_last = json.loads(
        (GOLDEN / "serve.jsonl").read_text().splitlines()[-1])
    s = summarize(_streams())
    assert s["train"]["final_loss"] == train["loss"]
    assert s["train"]["tokens_per_s"]["final"] == train["tokens_per_s"]
    assert s["serve"]["pool_utilization"]["final"] == serve_last["pool_util"]
    # and the text carries them at full repr precision
    text = render_text(s)
    assert repr(train["loss"]) in text
    assert repr(serve_last["pool_util"]) in text


def test_report_text_matches_committed_golden():
    """CI golden check: the rendered report of the committed streams must
    be byte-identical to the committed report.txt.  Regenerate with
    ``python -m repro.telemetry.report tests/telemetry/golden/*.jsonl``
    if you change the renderer on purpose."""
    got = render_text(summarize(_streams()))
    assert got == (GOLDEN / "report.txt").read_text()


def test_summary_sections_and_ranking():
    s = summarize(_streams())
    assert s["schema_versions"] == [1]
    assert s["train"]["steps"] == 4
    assert s["train"]["probes"]["opt_health"]["records"] == 2
    assert s["train"]["events"] == {"straggler": 1}
    assert s["serve"]["samples"] == 3
    assert s["serve"]["queue_depth_max"] == 2
    kn = s["kernels"]
    assert kn["launches"] == 3
    # measured launches first (wall_us desc), analytic rows after
    walls = [r.get("wall_us") for r in kn["ranked"]]
    assert walls[:2] == sorted(walls[:2], reverse=True)
    assert walls[-1] is None


def test_merging_split_streams_equals_one_stream(tmp_path):
    """A run killed and resumed produces one file, but report must also
    merge a stream split across files to the same summary."""
    lines = (GOLDEN / "train.jsonl").read_text().splitlines()
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text("\n".join(lines[:4]) + "\n")
    b.write_text(lines[0] + "\n" + "\n".join(lines[4:]) + "\n")
    merged = summarize([read_stream(a), read_stream(b)])
    whole = summarize([read_stream(GOLDEN / "train.jsonl")])
    assert merged["train"] == whole["train"]


def test_cli_json_out(tmp_path, capsys):
    out = tmp_path / "summary.json"
    rc = main([str(GOLDEN / "train.jsonl"), str(GOLDEN / "serve.jsonl"),
               "--json", "--out", str(out)])
    assert rc == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed == json.loads(out.read_text())
    assert printed["train"]["final_loss"] == 5.230990409851074
