"""The one traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

Training mixes give the batch shape, filled with whole rows of a
synthetic language (Zipf unigrams, a bigram rotation, copy spans; the
structure of the program's synthetic stream).  Serving mixes give an
open-loop arrival rate and lognormal prompt and output lengths.

Every seed gets the same work at the same times: a serving mix's arrival
times and request sizes, in their order, are drawn from the mix's own
``mix_seed``, and the run's ``--seed`` draws only the token ids (and the
weights).  A tail latency at four fifths of the engine's capacity turns on
which requests arrive together, so a schedule that moved with the seed
would move the tail with it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _rng(*keys) -> np.random.Generator:
    return np.random.default_rng([int(k) for k in keys])


# --------------------------------------------------------------------------
# The synthetic language
# --------------------------------------------------------------------------

class Language:
    """Zipf unigrams; with probability ``bigram_share`` the next token is a
    fixed rotation of the previous one; in every ``copy_period`` the
    second half repeats the first."""

    def __init__(self, vocab: int, spec: dict):
        rng = _rng(spec["mix_seed"], 1)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(spec.get("zipf", 1.0))
        self.cdf = np.cumsum(p / p.sum())
        self.rot = rng.permutation(vocab).astype(np.int32)
        self.bigram_share = float(spec.get("bigram_share", 0.5))
        self.copy_period = int(spec.get("copy_period", 64))
        self.vocab = vocab

    def rows(self, rng: np.random.Generator, n_rows: int, n: int
             ) -> np.ndarray:
        """``n_rows`` sequences of ``n`` tokens."""
        base = np.searchsorted(self.cdf, rng.random((n_rows, n)))
        base = np.minimum(base, self.vocab - 1).astype(np.int32)
        use_rot = rng.random((n_rows, n)) < self.bigram_share
        for t in range(1, n):
            sel = use_rot[:, t]
            base[sel, t] = self.rot[base[sel, t - 1]]
        half = self.copy_period // 2
        for start in range(0, n - self.copy_period + 1, self.copy_period):
            base[:, start + half:start + self.copy_period] = \
                base[:, start:start + half]
        return base


# --------------------------------------------------------------------------
# Training batches
# --------------------------------------------------------------------------

class TrainTraffic:
    """Batches for a training mix; ``batch(step)`` is pure in (seed, step)
    and every row of every step differs."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        self.spec = spec
        self.B, self.S = int(spec["batch"]), int(spec["seq_len"])
        self.lang = Language(vocab, spec)
        self.seed = seed

    def batch(self, step: int) -> dict:
        rng = _rng(self.seed, 4, step)
        seq = self.lang.rows(rng, self.B, self.S + 1)
        return {"tokens": seq[:, :-1].copy(), "labels": seq[:, 1:].copy()}


def real_tokens(batch: dict) -> int:
    """Tokens that carry a loss target."""
    return int((batch["labels"] >= 0).sum())


# --------------------------------------------------------------------------
# Serving requests
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Arrival:
    due: float            # seconds after the lead-in starts
    prompt: list
    max_new_tokens: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def serve_arrivals(spec: dict, vocab: int, seed: int, seconds: float
                   ) -> list[Arrival]:
    """Open-loop Poisson arrivals over ``lead_in_s + seconds`` at
    ``rate``: the gaps and the (prompt, output) sizes, in their order, come
    from the mix's ``mix_seed``; the token ids from ``seed``."""
    span = float(spec["lead_in_s"]) + float(seconds)
    n = max(1, int(round(float(spec["rate"]) * span)))
    mrng = _rng(spec["mix_seed"], 5, n)
    gaps = mrng.exponential(1.0, n)
    gaps *= span / gaps.sum()
    prompts = _lognormal(mrng, spec["prompt"], n)
    outputs = _lognormal(mrng, spec["output"], n)
    due = np.cumsum(gaps) - gaps[0]
    rng = _rng(seed, 6)
    lang = Language(vocab, spec)
    return [Arrival(float(d), lang.rows(rng, 1, int(p))[0].tolist(), int(o))
            for d, p, o in zip(due, prompts, outputs)]
