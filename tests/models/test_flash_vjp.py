"""Flash custom-VJP (recompute-in-backward) vs direct-attention autodiff."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


def _setup(S=64, B=2, K=2, G=2, dh=16, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, K, G, dh))
    k = jax.random.normal(ks[1], (B, S, K, dh))
    v = jax.random.normal(ks[2], (B, S, K, dh))
    pos = jnp.arange(S, dtype=jnp.int32)
    return q, k, v, pos


@pytest.mark.parametrize("spec,pl", [
    (L.MaskSpec(causal=True), None),
    (L.MaskSpec(causal=True, window=9), None),
    (L.MaskSpec(causal=True, has_prefix=True), np.array([5, 23])),
    (L.MaskSpec(causal=False), None),
])
@pytest.mark.parametrize("tiles", [1, 2, 4])
def test_flash_grads_match_direct(spec, pl, tiles):
    q, k, v, pos = _setup()
    dh = q.shape[-1]
    plj = jnp.asarray(pl) if pl is not None else None

    def f_flash(q, k, v):
        o = L._flash_attention(q, k, v, pos, pos, spec, plj, dh ** -0.5,
                               16, 16, tiles=tiles)
        return jnp.sum(o * jnp.cos(o))

    def f_direct(q, k, v):
        m = L._mask_block(pos, pos, spec, plj)
        m = m[None, None, None] if m.ndim == 2 else m[:, None, None]
        o = L._direct_attention(q, k, v, m, dh ** -0.5)
        return jnp.sum(o * jnp.cos(o))

    v1, g1 = jax.value_and_grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    v2, g2 = jax.value_and_grad(f_direct, argnums=(0, 1, 2))(q, k, v)
    # scalar is a sum over B*S*K*G*dh fp32 terms in different association
    # orders (blockwise online softmax vs direct); 1e-5 sat exactly on the
    # observed prefix-LM error (1.33e-5) — 5e-5 bounds reorder noise
    np.testing.assert_allclose(v1, v2, rtol=5e-5)
    for a, b, nm in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=f"d{nm}")


def test_flash_non_divisible_blocks():
    """Edge shapes: S not a multiple of the block size."""
    q, k, v, pos = _setup(S=50)
    dh = q.shape[-1]
    spec = L.MaskSpec(causal=True)

    def f(q, k, v, impl):
        if impl == "flash":
            o = L._flash_attention(q, k, v, pos, pos, spec, None,
                                   dh ** -0.5, 16, 16, tiles=1)
        else:
            m = L._mask_block(pos, pos, spec, None)[None, None, None]
            o = L._direct_attention(q, k, v, m, dh ** -0.5)
        return jnp.sum(jnp.tanh(o))

    v1, g1 = jax.value_and_grad(f)(q, k, v, "flash")
    v2, g2 = jax.value_and_grad(f)(q, k, v, "direct")
    np.testing.assert_allclose(v1, v2, rtol=1e-5)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# Dispatch: the Pallas flash kernels on TPU, the jnp paths elsewhere
# --------------------------------------------------------------------------

def _danube_train_args(S=4096, Skv=None):
    """Abstract q/k/v of h2o-danube-1.8b's training attention (B=4)."""
    B, H, K, dh = 4, 32, 8, 80
    Skv = S if Skv is None else Skv
    return (jax.ShapeDtypeStruct((B, S, H, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, Skv, K, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, Skv, K, dh), jnp.bfloat16))


def _takes_kernel(q, k, v, **kw):
    kw.setdefault("spec", L.MaskSpec(causal=True, window=4096))
    q_pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: L.attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, **kw))(q, k, v)
    return "pallas_call" in str(jaxpr)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_dispatch_takes_kernel_for_danube_training(on_tpu):
    assert _takes_kernel(*_danube_train_args())


@pytest.mark.parametrize("shape", [(2, 2), (1, 4), (1, 8)],
                         ids=["data2_model2", "model4", "model8"])
def test_dispatch_takes_sharded_kernel_under_mesh(on_tpu, shape):
    """A mesh whose ``model`` axis divides danube's 8 kv heads runs the
    kernels inside ``shard_map``, each device on its own heads."""
    mesh = jax.sharding.AbstractMesh(shape, ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh):
        q, k, v = _danube_train_args()
        q_pos = jnp.arange(q.shape[1], dtype=jnp.int32)
        jaxpr = str(jax.make_jaxpr(lambda q, k, v: L.attention(
            q, k, v, spec=L.MaskSpec(causal=True, window=4096), q_pos=q_pos,
            kv_pos=q_pos))(q, k, v))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr


@pytest.mark.parametrize("path", ["prefix_lm", "sq_ne_skv", "seq_tiles",
                                  "no_flash_vjp", "short", "mesh",
                                  "mesh_kv_heads_indivisible",
                                  "mesh_model_axis_of_one"])
def test_dispatch_keeps_jnp_path(on_tpu, monkeypatch, path):
    args, kw = _danube_train_args(), {}
    if path == "prefix_lm":
        kw = dict(spec=L.MaskSpec(causal=True, has_prefix=True),
                  prefix_len=jnp.full((4,), 100, jnp.int32))
    elif path == "sq_ne_skv":
        args = _danube_train_args(S=4096, Skv=4608)
    elif path == "seq_tiles":
        monkeypatch.setattr("repro.sharding.act.seq_tiles", lambda S: 2)
    elif path == "no_flash_vjp":
        kw = dict(use_flash_vjp=False)
    elif path == "short":
        args = _danube_train_args(S=2048)
    meshes = {"mesh": ((2,), ("data",)),                  # no tp axis
              "mesh_kv_heads_indivisible": ((1, 3), ("data", "model")),
              "mesh_model_axis_of_one": ((4, 1), ("data", "model"))}
    if path in meshes:
        mesh = jax.sharding.AbstractMesh(*meshes[path])
        with jax.sharding.use_abstract_mesh(mesh):
            assert not _takes_kernel(*args, **kw)
    else:
        assert not _takes_kernel(*args, **kw)


def test_dispatched_kernel_matches_jnp_path(monkeypatch):
    """attention() on a "TPU" with the kernels interpreted equals the jnp
    blockwise path, values and gradients, at S = 2304 (> 2048, padded)."""
    from jax.experimental.pallas import tpu as pltpu
    B, S, H, K, dh = 1, 2304, 2, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, K, dh))
    v = jax.random.normal(ks[2], (B, S, K, dh))
    pos = jnp.arange(S, dtype=jnp.int32)
    spec = L.MaskSpec(causal=True, window=2048)

    def loss(q, k, v):
        o = L.attention(q, k, v, spec=spec, q_pos=pos, kv_pos=pos)
        return jnp.sum(jnp.sin(o)), o

    (_, o_ref), g_ref = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _takes_kernel(q, k, v, spec=spec)
    with pltpu.force_tpu_interpret_mode():
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    for a, b, nm in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=f"d{nm}")
