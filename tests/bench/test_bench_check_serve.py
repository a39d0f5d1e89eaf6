"""A whole serving-cell run on the CPU at a tiny size, past the chip
check: sound, it is correct under the cell's limit; with one token
altered where the engine produces it, ``correct`` comes out false."""
import numpy as np

from _tiny import TINY, execute, serve_traffic

CELL = "serve.danube-1.8b.chat"


def altered_token(engine):
    collect = engine._collect

    def _collect(toks):
        toks = np.array(toks)
        live = toks >= 0
        toks[live] = (toks[live] + 1) % TINY["vocab_size"]
        collect(toks)

    engine._collect = _collect


def test_sound_run_is_correct():
    out = execute(CELL, serve_traffic(), seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_p90_ms",
                                   "peak_hbm_gib"}


def test_altered_token_is_not_correct():
    out = execute(CELL, serve_traffic(), seconds=2.0, fault=altered_token)
    assert not out["correct"]
    c = out["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]
