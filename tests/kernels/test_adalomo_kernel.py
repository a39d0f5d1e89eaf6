"""Pallas AdaLomo kernel vs the pure-jnp oracle (interpret mode on CPU):
shape × dtype sweeps + hypothesis edge shapes + backend-dispatch drop-in."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.adalomo import AdaLomoConfig
from repro.kernels.adalomo_update.ops import adalomo_update
from repro.kernels.adalomo_update.ref import adalomo_update_ref

SHAPES = [(64, 128), (256, 512), (300, 700), (128, 130), (1000, 96),
          (16, 4096)]


def _mk(key, m, n, pdtype, gdtype, step):
    ks = jax.random.split(key, 4)
    p = (jax.random.normal(ks[0], (m, n), jnp.float32) * 0.1).astype(pdtype)
    g = (jax.random.normal(ks[1], (m, n), jnp.float32) * 0.3).astype(gdtype)
    r = jax.random.uniform(ks[2], (m,), jnp.float32) * (step > 1) * 1e-2
    c = jax.random.uniform(ks[3], (n,), jnp.float32) * (step > 1) * 1e-2
    return p, g, r, c


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("pdtype,gdtype", [(jnp.float32, jnp.float32),
                                           (jnp.bfloat16, jnp.bfloat16),
                                           (jnp.float32, jnp.bfloat16)])
def test_kernel_matches_oracle(shape, pdtype, gdtype):
    m, n = shape
    key = jax.random.PRNGKey(m * 7 + n)
    for step in (1.0, 5.0):
        p, g, r, c = _mk(key, m, n, pdtype, gdtype, step)
        pk, rk, ck = adalomo_update(p, g, r, c, 5e-4, step,
                                    interpret=True, block=(128, 256))
        pr, rr, cr = adalomo_update_ref(p, g, r, c, lr=5e-4, step=step)
        tol = 1e-5 if pdtype == jnp.float32 else 5e-3
        np.testing.assert_allclose(
            np.asarray(pk, np.float32), np.asarray(pr, np.float32),
            rtol=tol, atol=tol)
        # r/c: blockwise vs single-pass reduction order → ~1e-5 relative
        np.testing.assert_allclose(rk, rr, rtol=3e-5, atol=1e-5)
        np.testing.assert_allclose(ck, cr, rtol=3e-5, atol=1e-5)


def test_stacked_vmap_path():
    key = jax.random.PRNGKey(0)
    L, m, n = 3, 96, 160
    p = jax.random.normal(key, (L, m, n)) * 0.1
    g = jax.random.normal(jax.random.fold_in(key, 1), (L, m, n))
    r = jnp.zeros((L, m))
    c = jnp.zeros((L, n))
    pk, rk, ck = adalomo_update(p, g, r, c, 1e-3, 1.0, interpret=True,
                                block=(64, 128))
    for i in range(L):
        pr, rr, cr = adalomo_update_ref(p[i], g[i], r[i], c[i], lr=1e-3,
                                        step=1.0)
        np.testing.assert_allclose(pk[i], pr, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(rk[i], rr, rtol=1e-5, atol=1e-6)


def test_literal_mode_matches_oracle():
    key = jax.random.PRNGKey(5)
    p, g, r, c = _mk(key, 64, 128, jnp.float32, jnp.float32, 2.0)
    cfg = AdaLomoConfig(literal_div_v=True)
    pk, rk, ck = adalomo_update(p, g, r, c, 1e-3, 2.0, cfg=cfg,
                                interpret=True, block=(64, 128))
    pr, rr, cr = adalomo_update_ref(p, g, r, c, lr=1e-3, step=2.0, cfg=cfg)
    np.testing.assert_allclose(pk, pr, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("lr,wd", [(1e-3, 0.1), (0.1, 0.5)])
def test_weight_decay_parity(lr, wd):
    """Kernel == oracle with weight_decay > 0 at *tight* tolerance.

    Regression for the pre-v2 divergence: the kernel used to pre-scale θ by
    (1 - lr·wd) before accumulating Σθ², so its RMS(θ) trust scale came
    from the decayed θ while the oracle's came from the un-decayed θ.  At
    lr=0.1, wd=0.5 that is a 5% scale error — far outside this tolerance.
    """
    key = jax.random.PRNGKey(6)
    p, g, r, c = _mk(key, 96, 160, jnp.float32, jnp.float32, 2.0)
    pk, rk, ck = adalomo_update(p, g, r, c, lr, 2.0, 0.999, wd, 1.0,
                                interpret=True, block=(64, 128))
    pr, rr, cr = adalomo_update_ref(p, g, r, c, lr=lr, step=2.0,
                                    weight_decay=wd)
    np.testing.assert_allclose(pk, pr, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(rk, rr, rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(ck, cr, rtol=2e-5, atol=2e-7)


def test_dynamic_hparams_are_traced_operands():
    """lr/β/wd/clip are kernel operands, not compile-time constants:
    changing them between calls must not recompile the jitted wrapper."""
    key = jax.random.PRNGKey(7)
    p, g, r, c = _mk(key, 64, 128, jnp.float32, jnp.float32, 2.0)

    @jax.jit
    def step(p, g, r, c, lr, beta, wd, clip):
        return adalomo_update(p, g, r, c, lr, 2.0, beta, wd, clip,
                              interpret=True, block=(64, 128))

    outs = [step(p, g, r, c, jnp.float32(lr), jnp.float32(b),
                 jnp.float32(w), jnp.float32(cl))
            for lr, b, w, cl in [(1e-3, 0.999, 0.0, 1.0),
                                 (5e-4, 0.99, 0.1, 2.0),
                                 (1e-2, 0.9, 0.3, 0.5)]]
    assert step._cache_size() == 1, "hparam change recompiled the kernel"
    # and each matches the oracle at its own hparams
    for (pk, _, _), (lr, b, w, cl) in zip(
            outs, [(1e-3, 0.999, 0.0, 1.0), (5e-4, 0.99, 0.1, 2.0),
                   (1e-2, 0.9, 0.3, 0.5)]):
        pr, _, _ = adalomo_update_ref(p, g, r, c, lr=lr, step=2.0, beta=b,
                                      weight_decay=w, clip=cl)
        np.testing.assert_allclose(pk, pr, rtol=2e-5, atol=2e-6)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(8, 200), n=st.integers(8, 300),
       bm=st.sampled_from([32, 64, 128]), bn=st.sampled_from([64, 128]))
def test_property_block_edges(m, n, bm, bn):
    """Any (shape, block) combination — incl. non-divisible edges — matches
    the oracle."""
    key = jax.random.PRNGKey(m * 1000 + n)
    p, g, r, c = _mk(key, m, n, jnp.float32, jnp.float32, 3.0)
    pk, rk, ck = adalomo_update(p, g, r, c, 1e-3, 3.0, interpret=True,
                                block=(bm, bn))
    pr, rr, cr = adalomo_update_ref(p, g, r, c, lr=1e-3, step=3.0)
    np.testing.assert_allclose(pk, pr, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(rk, rr, rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(ck, cr, rtol=2e-5, atol=2e-7)


def test_pallas_backend_drop_in_trains():
    """get_rule('adalomo', backend='pallas') is the same rule — it slots
    into the fused engine over the same OptState and reproduces the jnp
    backend's trajectory (the kernel is a dispatch, not a second rule)."""
    from repro.core import optimizers as opt_lib
    from repro.models.registry import get_arch
    arch = get_arch("h2o-danube-1.8b", smoke=True)
    key = jax.random.PRNGKey(0)
    params = arch.init_params(key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, arch.cfg.vocab),
             "labels": jax.random.randint(key, (2, 16), 0, arch.cfg.vocab)}
    results = []
    state_trees = []
    for opt in (opt_lib.get_opt("adalomo", backend="jnp"),
                opt_lib.get_opt("adalomo", backend="pallas",
                                interpret=True, block=(128, 256))):
        opt_state = opt.init(params)
        step = arch.make_fused_train_step(opt)
        p, s = params, opt_state
        for _ in range(2):
            p, s, loss, _ = jax.jit(
                lambda pp, ss, bb: step(pp, ss, bb,
                                        hparams=jnp.float32(1e-3))
            )(p, s, batch)
        results.append((float(loss), p))
        state_trees.append(s)
    assert abs(results[0][0] - results[1][0]) < 1e-4
    for a, b in zip(jax.tree.leaves(results[0][1]),
                    jax.tree.leaves(results[1][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
    # one state layout: identical treedefs across backends
    assert (jax.tree.structure(state_trees[0])
            == jax.tree.structure(state_trees[1]))
