"""Flash-decoding Pallas kernel vs oracle: shape/dtype/window sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas)
from repro.kernels.decode_attention.ref import decode_attention_ref

CASES = [
    # B, W, H, K, dh, window, cur
    (2, 128, 8, 2, 64, None, 100),
    (1, 300, 4, 4, 128, None, 250),
    (3, 512, 16, 4, 64, 64, 400),
    (2, 64, 8, 8, 32, None, 10),
    (1, 1024, 32, 8, 128, 256, 900),
]


def _mk(key, B, W, H, K, dh, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    kc = jax.random.normal(ks[1], (B, W, K, dh), dtype)
    vc = jax.random.normal(ks[2], (B, W, K, dh), dtype)
    return q, kc, vc


@pytest.mark.parametrize("B,W,H,K,dh,window,cur", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matches_oracle(B, W, H, K, dh, window, cur, dtype):
    q, kc, vc = _mk(jax.random.PRNGKey(B * W), B, W, H, K, dh, dtype)
    pos = jnp.where(jnp.arange(W) <= cur, jnp.arange(W), -1)
    out_k = decode_attention_pallas(q, kc, vc, pos, float(cur),
                                    window=window, kv_block=128,
                                    interpret=True)
    out_r = decode_attention_ref(
        q, kc, vc, kv_pos=jnp.broadcast_to(pos[None], (B, W)),
        q_pos=jnp.full((B,), cur, jnp.int32), window=window)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=12, deadline=None)
@given(W=st.integers(16, 400), K=st.sampled_from([1, 2, 4]),
       G=st.sampled_from([1, 2, 4]), dh=st.sampled_from([32, 64]),
       kv_block=st.sampled_from([32, 128]))
def test_property_ragged_cache(W, K, G, dh, kv_block):
    """Partially-filled ring caches with arbitrary W vs block sizes."""
    B, H = 2, K * G
    cur = max(W // 2, 1)
    q, kc, vc = _mk(jax.random.PRNGKey(W * K), B, W, H, K, dh, jnp.float32)
    pos = jnp.where(jnp.arange(W) <= cur, jnp.arange(W), -1)
    out_k = decode_attention_pallas(q, kc, vc, pos, float(cur),
                                    kv_block=kv_block, interpret=True)
    out_r = decode_attention_ref(
        q, kc, vc, kv_pos=jnp.broadcast_to(pos[None], (B, W)),
        q_pos=jnp.full((B,), cur, jnp.int32))
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-5)
