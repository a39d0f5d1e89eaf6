"""Unit + property tests for the AdaLomo optimizer math (paper Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.adalomo import (DEFAULT_HPARAMS, AdaLomoConfig,
                                FactoredState, init_state, reconstruct_v,
                                state_bytes, update_moment, update_tensor)

CFG = AdaLomoConfig()


def test_state_is_o_m_plus_n():
    p = jnp.zeros((512, 1024))
    st_ = init_state(p, CFG)
    assert st_.r.shape == (512,) and st_.c.shape == (1024,)
    assert st_.v is None
    # Table 1: optimizer state negligible vs 4·m·n bytes of fp32 params
    assert state_bytes(p, CFG) == (512 + 1024) * 4


def test_1d_param_unfactored():
    p = jnp.zeros((768,))
    st_ = init_state(p, CFG)
    assert st_.v.shape == (768,) and st_.r is None


def test_stacked_param_factors_trailing_dims():
    p = jnp.zeros((4, 64, 128))
    st_ = init_state(p, CFG)
    assert st_.r.shape == (4, 64) and st_.c.shape == (4, 128)


def test_moment_update_matches_paper_eq67():
    g = jnp.array([[1.0, 2.0], [3.0, 4.0]])
    st0 = FactoredState(r=jnp.array([1.0, 1.0]), c=jnp.array([2.0, 2.0]),
                        v=None)
    cfg = AdaLomoConfig(eps_stat=0.0, min_dim_size_to_factor=1)
    st1 = update_moment(g, st0, beta=0.9, cfg=cfg)
    np.testing.assert_allclose(st1.r, 0.9 * 1.0 + 0.1 * jnp.array([5., 25.]))
    np.testing.assert_allclose(st1.c, 0.9 * 2.0 + 0.1 * jnp.array([10., 20.]))


def test_reconstruction_exact_for_rank1():
    """v = outer(r,c)/sum(r) recovers g² exactly when g² is rank-1 (Eq.5)."""
    a = jnp.array([1.0, 2.0, 4.0])
    b = jnp.array([0.5, 3.0])
    g = jnp.sqrt(jnp.outer(a, b))
    cfg = AdaLomoConfig(eps_stat=0.0, min_dim_size_to_factor=1,
                        bias_correction=False)
    st0 = FactoredState(r=jnp.zeros(3), c=jnp.zeros(2), v=None)
    st1 = update_moment(g, st0, beta=0.0, cfg=cfg)
    v = reconstruct_v(st1, cfg)
    np.testing.assert_allclose(v, jnp.outer(a, b), rtol=1e-6)


def test_grouped_norm_bounds_update_rms():
    """Alg.1 line 11: RMS of the applied update ≤ clip · max(ε₂, RMS(θ))."""
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (64, 64)) * 0.05
    g = jax.random.normal(jax.random.fold_in(key, 1), (64, 64)) * 100.0
    st0 = init_state(p, CFG)
    new_p, _ = update_tensor(p, g, st0, lr=jnp.float32(1.0),
                             step=jnp.float32(1), cfg=CFG)
    upd = (p - new_p)
    rms_upd = float(jnp.sqrt(jnp.mean(upd ** 2)))
    rms_p = float(jnp.sqrt(jnp.mean(p ** 2)))
    clip = DEFAULT_HPARAMS["clip"]
    assert rms_upd <= clip * max(CFG.eps_rms, rms_p) * 1.01


def test_update_scale_invariant_to_grad_scale():
    """With bias correction at t=1, û depends only on the *direction*
    structure of g (v̂ ≈ g²), so scaling g by 1000 barely changes the step —
    the adaptive-lr property that separates AdaLomo from LOMO/SGD."""
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (32, 32))
    g = jax.random.normal(jax.random.fold_in(key, 1), (32, 32))
    st0 = init_state(p, CFG)
    p1, _ = update_tensor(p, g, st0, lr=jnp.float32(1e-2),
                          step=jnp.float32(1), cfg=CFG)
    p2, _ = update_tensor(p, g * 1000.0, st0, lr=jnp.float32(1e-2),
                          step=jnp.float32(1), cfg=CFG)
    np.testing.assert_allclose(p1, p2, rtol=1e-3)


def test_literal_div_v_mode_differs():
    cfg_lit = AdaLomoConfig(literal_div_v=True)
    key = jax.random.PRNGKey(2)
    p = jax.random.normal(key, (16, 16))
    g = jax.random.normal(jax.random.fold_in(key, 3), (16, 16))
    s0 = init_state(p, CFG)
    a, _ = update_tensor(p, g, s0, lr=jnp.float32(1e-3),
                         step=jnp.float32(1), cfg=CFG)
    b, _ = update_tensor(p, g, s0, lr=jnp.float32(1e-3),
                         step=jnp.float32(1), cfg=cfg_lit)
    assert not np.allclose(a, b)


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 48), n=st.integers(1, 48),
       scale=st.floats(1e-6, 1e3),
       zero_grad=st.booleans(), steps=st.integers(1, 4))
def test_property_no_nans_and_state_shape(m, n, scale, zero_grad, steps):
    """For any shape/scale (incl. zero grads), updates stay finite and the
    state layout is O(m+n) (or O(mn) only below the factor threshold)."""
    key = jax.random.PRNGKey(m * 100 + n)
    p = jax.random.normal(key, (m, n)) * 0.1
    g = jnp.zeros((m, n)) if zero_grad else \
        jax.random.normal(jax.random.fold_in(key, 7), (m, n)) * scale
    s = init_state(p, CFG)
    n_state = sum(x.size for x in jax.tree.leaves(s))
    if min(m, n) >= CFG.min_dim_size_to_factor:
        assert n_state == m + n
    else:
        assert n_state == m * n
    for t in range(1, steps + 1):
        p, s = update_tensor(p, g, s, lr=jnp.float32(1e-3),
                             step=jnp.float32(t), cfg=CFG)
    assert bool(jnp.isfinite(p).all())
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(s))
