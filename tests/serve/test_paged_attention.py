"""Paged decode attention vs the dense oracle, for ragged sequence
lengths and shuffled page assignments (kernel in interpret mode + jnp
gather path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.decode_attention import (
    paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)

CASES = [
    # B, H, K, dh, page_size, P, window, seq_lens
    (3, 8, 2, 64, 8, 4, None, (19, 9, 25)),
    (2, 4, 4, 32, 16, 2, None, (1, 32)),
    (4, 8, 8, 64, 4, 8, 6, (30, 3, 17, 8)),
    (1, 16, 4, 128, 8, 3, None, (24,)),
]


def _scatter_setup(key, B, H, K, dh, ps, P, seq_lens):
    """Dense per-seq caches + the same data scattered into shuffled pages."""
    rng = np.random.RandomState(int(jax.random.randint(key, (), 0, 1 << 30)))
    W = P * ps
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kc = np.array(jax.random.normal(ks[1], (B, W, K, dh), jnp.float32))
    vc = np.array(jax.random.normal(ks[2], (B, W, K, dh), jnp.float32))
    # zero out positions past seq_len so garbage can't hide a masking bug
    for b, n in enumerate(seq_lens):
        kc[b, n:] = 0.0
        vc[b, n:] = 0.0
    N = 1 + B * P                      # page 0 = scratch
    perm = rng.permutation(np.arange(1, N))
    bt = perm.reshape(B, P).astype(np.int32)
    k_pages = rng.normal(size=(N, K, ps, dh)).astype(np.float32)  # garbage
    v_pages = rng.normal(size=(N, K, ps, dh)).astype(np.float32)
    for b in range(B):
        for p in range(P):
            k_pages[bt[b, p]] = kc[b, p * ps:(p + 1) * ps].transpose(1, 0, 2)
            v_pages[bt[b, p]] = vc[b, p * ps:(p + 1) * ps].transpose(1, 0, 2)
    return (q, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(bt),
            jnp.asarray(seq_lens, dtype=jnp.int32))


@pytest.mark.parametrize("B,H,K,dh,ps,P,window,seq_lens", CASES)
def test_paged_matches_dense_oracle(B, H, K, dh, ps, P, window, seq_lens):
    key = jax.random.PRNGKey(B * 31 + P)
    q, kc, vc, kp, vp, bt, sl = _scatter_setup(key, B, H, K, dh, ps, P,
                                               seq_lens)
    W = P * ps
    pos = jnp.arange(W, dtype=jnp.int32)
    kv_pos = jnp.where(pos[None] < sl[:, None], pos[None], -1)
    dense = decode_attention_ref(q, kc, vc, kv_pos=kv_pos,
                                 q_pos=sl - 1, window=window)
    paged_jnp = paged_decode_attention_ref(q, kp, vp, bt, sl, window=window)
    paged_krn = paged_decode_attention_pallas(q, kp, vp, bt, sl,
                                              window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(paged_jnp), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(paged_krn), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_paged_ignores_scratch_garbage():
    """Unallocated block-table tail entries point at scratch page 0; junk
    there must never leak into the output."""
    B, H, K, dh, ps, P = 2, 4, 2, 32, 8, 4
    key = jax.random.PRNGKey(7)
    seq_lens = (5, 11)
    q, kc, vc, kp, vp, bt, sl = _scatter_setup(key, B, H, K, dh, ps, P,
                                               seq_lens)
    out1 = paged_decode_attention_ref(q, kp, vp, bt, sl)
    kp2 = kp.at[0].set(1e9)
    vp2 = vp.at[0].set(-1e9)
    bt2 = bt.at[:, 2:].set(0)          # tail -> scratch (lens fit 2 pages)
    out2 = paged_decode_attention_ref(q, kp2, vp2, bt2, sl)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
