"""Model FLOPs of a dense decoder, counted from the configuration and the
traffic alone (the yardstick of every ``mfu.*`` metric).

A multiply-add is 2 FLOPs.  Counted: every weight matmul on every real
token, the language-model head on the positions whose logits the work
needs, and attention's two products over the keys each query may see
(causal, inside its own document, inside the window).  Not counted:
norms, activations, softmax, the embedding gather, recomputation, and
padding or bucket slots.  Training is forward plus backward: 3x forward.
"""
from __future__ import annotations

from bench.weights import dims


def matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through the blocks (head excluded)."""
    m = dims(cfg)
    d, f, qd, kd = m["d"], m["f"], m["H"] * m["dh"], m["K"] * m["dh"]
    return m["L"] * (2 * d * qd + 2 * d * kd + 3 * d * f)


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["V"]


def visible_keys(n: int, window) -> int:
    """Keys seen by the queries of one causal document of n tokens."""
    if not window or window >= n:
        return n * (n + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (n - w) * w


def attention_flops(cfg: dict, keys: int) -> int:
    """Forward QK^T and PV over ``keys`` (query, key) pairs, all layers."""
    m = dims(cfg)
    return 4 * m["H"] * m["dh"] * keys * m["L"]


def forward_flops(cfg: dict, doc_lens, head_tokens=None) -> int:
    """Forward FLOPs over documents of the given lengths; the head runs on
    every token unless ``head_tokens`` says how many."""
    n_tok = sum(doc_lens)
    head_tokens = n_tok if head_tokens is None else head_tokens
    keys = sum(visible_keys(n, cfg.get("sliding_window")) for n in doc_lens)
    return (2 * matmul_params(cfg) * n_tok + 2 * head_params(cfg)
            * head_tokens + attention_flops(cfg, keys))


def train_flops(cfg: dict, batch: dict) -> int:
    """Forward + backward of one training batch: each row one document."""
    B, S = batch["tokens"].shape
    return 3 * forward_flops(cfg, [S] * B)


def decode_step_flops(cfg: dict, cached: list[int]) -> int:
    """One decode step: each live row's new token against its cache of
    ``cached`` tokens (itself included)."""
    return (2 * (matmul_params(cfg) + head_params(cfg)) * len(cached)
            + attention_flops(cfg, sum(cached)))


def prefill_flops(cfg: dict, n: int) -> int:
    """One prompt of n real tokens; logits only at its last position."""
    return forward_flops(cfg, [n], head_tokens=1)
