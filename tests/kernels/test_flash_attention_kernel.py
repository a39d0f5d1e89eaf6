"""Flash-attention Pallas kernels (interpret mode) vs the jnp direct
attention of ``models/layers.py``: out, lse and the gradients dq, dk, dv."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops
from repro.kernels.flash_attention.ref import flash_attention_ref

# name: B, S, K, G, dh, causal, window, packed, dtype, block_q, block_kv
CASES = {
    "causal_g4_dh80": (2, 64, 2, 4, 80, True, None, False, jnp.float32, 16,
                       32),
    "causal_g1_dh128": (1, 64, 2, 1, 128, True, None, False, jnp.float32, 16,
                        16),
    "window_g4_dh80": (1, 96, 1, 4, 80, True, 20, False, jnp.float32, 16, 32),
    "packed_g4_dh80": (2, 64, 2, 4, 80, True, None, True, jnp.float32, 16,
                       16),
    "packed_window_g1": (1, 80, 2, 1, 128, True, 12, True, jnp.float32, 16,
                         32),
    "pad_s50_g4": (1, 50, 2, 4, 80, True, None, False, jnp.float32, 16, 32),
    "pad_s50_noncausal": (1, 50, 1, 4, 80, False, None, False, jnp.float32,
                          16, 32),
    "bf16_window_g4_dh80": (1, 96, 2, 4, 80, True, 40, False, jnp.bfloat16,
                            32, 32),
    "bf16_packed_g1_dh128": (2, 64, 1, 1, 128, True, None, True,
                             jnp.bfloat16, 16, 32),
}


def _inputs(B, S, K, G, dh, packed, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, dh), jnp.float32).astype(dtype)
    if not packed:
        return q, k, v, jnp.arange(S, dtype=jnp.int32), None
    # three documents per row, boundaries off the block grid, different
    # per row; positions restart in each document
    cuts = [(0, 21, 45), (0, 9, 40)][:B]
    seg = np.zeros((B, S), np.int32)
    pos = np.zeros((B, S), np.int32)
    for b, starts in enumerate(cuts):
        for d, s0 in enumerate(starts):
            seg[b, s0:] = d
            pos[b, s0:] = np.arange(S - s0)
    return q, k, v, jnp.asarray(pos), jnp.asarray(seg)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.mark.parametrize("case", list(CASES))
def test_matches_direct_attention(case):
    (B, S, K, G, dh, causal, window, packed, dtype, bq,
     bk) = CASES[case]
    q, k, v, pos, seg = _inputs(B, S, K, G, dh, packed, dtype)
    kw = dict(scale=dh ** -0.5, causal=causal, window=window, q_seg=seg,
              kv_seg=seg)
    out, lse = ops.flash_attention_lse(q, k, v, pos, pos, block_q=bq,
                                       block_kv=bk, interpret=True, **kw)
    out_r, lse_r = flash_attention_ref(q, k, v, pos, pos, **kw)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == v.dtype and out.shape == out_r.shape
    assert _rel(out, out_r) < tol
    np.testing.assert_allclose(lse, lse_r, rtol=1e-5, atol=1e-5)

    w = jax.random.normal(jax.random.PRNGKey(7), out.shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    grads = jax.grad(loss(lambda q, k, v: ops.flash_attention(
        q, k, v, pos, pos, block_q=bq, block_kv=bk, interpret=True, **kw)),
        argnums=(0, 1, 2))(q, k, v)
    grads_r = jax.grad(loss(lambda q, k, v: flash_attention_ref(
        q, k, v, pos, pos, **kw)[0]), argnums=(0, 1, 2))(q, k, v)
    for g, g_r, name in zip(grads, grads_r, ("dq", "dk", "dv")):
        assert g.dtype == dtype and g.shape == g_r.shape, name
        assert _rel(g, g_r) < (2e-5 if dtype == jnp.float32 else 3e-2), name


@pytest.mark.parametrize("where", ["above_diagonal", "outside_window"])
def test_fully_masked_block_contributes_nothing(where):
    """A (q block, kv block) pair the mask blanks whole is skipped: keys of
    1e4 and infinite values in that kv block leave the q block's output and
    dq as the reference computes them from the original inputs."""
    B, S, K, G, dh, blk = 1, 64, 1, 4, 80, 16
    q, k, v, pos, _ = _inputs(B, S, K, G, dh, False, jnp.float32, seed=3)
    if where == "above_diagonal":      # kv block 3 vs q blocks 0-2
        window, kv, rows = None, slice(48, 64), slice(0, 48)
    else:                              # kv block 0 vs q blocks 2-3
        window, kv, rows = 16, slice(0, 16), slice(32, 64)
    k_bad = k.at[:, kv].set(1e4)
    v_bad = v.at[:, kv].set(jnp.inf)
    kw = dict(scale=dh ** -0.5, causal=True, window=window)

    def rows_loss(fn, k, v):
        return lambda q: jnp.sum(jnp.tanh(fn(q, k, v)[:, rows]))

    kern = lambda q, k, v: ops.flash_attention(          # noqa: E731
        q, k, v, pos, pos, block_q=blk, block_kv=blk, interpret=True, **kw)
    refn = lambda q, k, v: flash_attention_ref(          # noqa: E731
        q, k, v, pos, pos, **kw)[0]
    out = kern(q, k_bad, v_bad)[:, rows]
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(out, refn(q, k, v)[:, rows], rtol=1e-5,
                               atol=1e-6)
    dq = jax.grad(rows_loss(kern, k_bad, v_bad))(q)[:, rows]
    dq_r = jax.grad(rows_loss(refn, k, v))(q)[:, rows]
    np.testing.assert_allclose(dq, dq_r, rtol=1e-4, atol=1e-6)
