"""Run by ``test_flash_attention_sharded.py`` in a process of its own with
four CPU devices: the flash-attention kernels (interpret mode) inside
``shard_map`` on a 2x2 (data, model) mesh, each device on its own kv heads,
against the same kernels on one device; and ``layers.attention`` on that
mesh, dispatched as on a TPU, against the ``jnp`` scan.  Prints one JSON
line: per case, the largest gaps of the output and of dq, dk, dv, and what
the dispatch chose."""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.kernels.flash_attention import ops  # noqa: E402
from repro.models import layers as L  # noqa: E402

# name: B, S, K, G, dh, window, packed, dtype (causal; blocks 16 x 32)
KERNEL_CASES = {
    "shared_pos": (2, 64, 4, 2, 128, None, False, jnp.float32),
    "packed": (2, 64, 4, 2, 128, None, True, jnp.float32),
    "window_pad": (2, 50, 2, 4, 80, 20, False, jnp.float32),
    "bf16_packed": (2, 64, 2, 2, 128, None, True, jnp.bfloat16),
}
# name: K kv heads (H = 2K) of attention() at S = 2304, on the mesh
DISPATCH_CASES = {"kv_heads_over_model": 2, "kv_heads_indivisible": 1}


def qkv(B, S, K, G, dh, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, dh), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (B, S, K, G, dh), jnp.float32)
    return q, k, v, w


def packed_ids(B, S):
    """Three documents per row, boundaries off the block grid, different
    per row; positions restart in each document."""
    seg = np.zeros((B, S), np.int32)
    pos = np.zeros((B, S), np.int32)
    for b, starts in enumerate([(0, 21, 45), (0, 9, 40)][:B]):
        for d, s0 in enumerate(starts):
            seg[b, s0:] = d
            pos[b, s0:] = np.arange(S - s0)
    return jnp.asarray(pos), jnp.asarray(seg)


def value_and_grads(fn, q, k, v, w):
    """The output and dq, dk, dv for the cotangent w.  No loss is summed
    over the devices: on CPU devices a collective waits on threads that
    may be busy in another device's interpreted kernel, so the mesh runs
    hold none."""
    def run(q, k, v, w):
        out, pullback = jax.vjp(fn, q, k, v)
        return (out,) + pullback(w.astype(out.dtype))
    return jax.jit(run)(q, k, v, w)


def gaps(got, want):
    return {name: float(np.max(np.abs(np.asarray(a, np.float32)
                                      - np.asarray(b, np.float32))))
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}


def kernel_case(mesh, B, S, K, G, dh, window, packed, dtype):
    q, k, v, w = qkv(B, S, K, G, dh, dtype, seed=S + K)
    if packed:
        pos, seg = packed_ids(B, S)
    else:
        pos, seg = jnp.arange(S, dtype=jnp.int32), None
    kw = dict(scale=dh ** -0.5, causal=True, window=window, q_seg=seg,
              kv_seg=seg, block_q=16, block_kv=32, interpret=True)
    one = value_and_grads(lambda q, k, v: ops.flash_attention(
        q, k, v, pos, pos, **kw), q, k, v, w)
    heads = NamedSharding(mesh, P(None, None, "model"))
    with jax.set_mesh(mesh):
        got = value_and_grads(lambda q, k, v: ops.flash_attention_sharded(
            q, k, v, pos, pos, mesh=mesh, head_axis="model", **kw),
            *(jax.device_put(x, heads) for x in (q, k, v, w)))
    return {"one_device": gaps(got, one),
            "out_spec": str(got[0].sharding.spec)}


def dispatch_case(mesh, K):
    """attention() at S = 2304 (> 2048: the flash branch), H = 2K heads of
    16: on the mesh with the backend reported as a TPU and the kernels
    interpreted, against the jnp scan on the CPU with no mesh."""
    B, S, dh = 2, 2304, 16
    q, k, v, _ = qkv(B, S, K, 2, dh, jnp.float32, seed=K)
    q = q.reshape(B, S, 2 * K, dh)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    spec = L.MaskSpec(causal=True, window=2048)

    def attend(q, k, v):
        return L.attention(q, k, v, spec=spec, q_pos=pos, kv_pos=pos)

    scan = value_and_grads(attend, q, k, v, w)
    # heads over ``model`` where they divide, as the step's projections
    # leave them
    heads = NamedSharding(mesh, P(None, None, "model") if K % 2 == 0
                          else P())
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        with jax.set_mesh(mesh), pltpu.force_tpu_interpret_mode():
            jaxpr = str(jax.make_jaxpr(attend)(q, k, v))
            got = value_and_grads(attend, *(jax.device_put(x, heads)
                                            for x in (q, k, v, w)))
    finally:
        jax.default_backend = backend
    return {"scan": gaps(got, scan), "kernel": "pallas_call" in jaxpr,
            "shard_map": "shard_map" in jaxpr}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    out = {name: kernel_case(mesh, *case)
           for name, case in KERNEL_CASES.items()}
    out.update({name: dispatch_case(mesh, K)
                for name, K in DISPATCH_CASES.items()})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
