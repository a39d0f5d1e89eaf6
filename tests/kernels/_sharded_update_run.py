"""Run by ``test_adalomo_sharded.py`` in a process of its own with four
CPU devices: the AdaLomo rule's Pallas kernel (interpret mode) on each
device's shard of a tensor on a 2x2 (data, model) mesh, under every spec
the partition rules give a 2-D leaf, against the whole-tensor update
(``kernels/adalomo_update/ref.py``, ``core.adalomo.update_tensor``) and
the kernel on one device.  Prints one JSON line: per case, the largest
gaps and the shardings the results came back in."""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import optimizers as O  # noqa: E402
from repro.core.adalomo import (AdaLomoConfig, FactoredState,  # noqa: E402
                                update_tensor)
from repro.kernels.adalomo_update.ops import adalomo_update  # noqa: E402
from repro.kernels.adalomo_update.ref import adalomo_update_ref  # noqa: E402

BLOCK = (64, 128)
SPECS = {"fsdp_tp": P("data", "model"), "tp_fsdp": P("model", "data"),
         "fsdp": P("data", None), "replicated": P()}
# (256, 512): each shard a whole number of blocks; (200, 300): shards of
# 100 x 150 or 200 x 150, padded to the block inside the kernel
SHAPES = {"blocks": (256, 512), "ragged": (200, 300)}
WEIGHT_DECAY = {"no_decay": 0.0, "decay": 0.1}


def inputs(m, n):
    k = jax.random.split(jax.random.PRNGKey(m * 7 + n), 4)
    p = jax.random.normal(k[0], (m, n), jnp.float32) * 0.1
    g = jax.random.normal(k[1], (m, n), jnp.float32) * 0.3
    r = jax.random.uniform(k[2], (m,), jnp.float32) * 1e-2
    c = jax.random.uniform(k[3], (n,), jnp.float32) * 1e-2
    return p, g, r, c


def gaps(got, want):
    (pk, rk, ck), (pw, rw, cw) = got, want
    return {"param": float(jnp.max(jnp.abs(pk - pw))),
            "r": float(jnp.max(jnp.abs(rk - rw) / jnp.abs(rw))),
            "c": float(jnp.max(jnp.abs(ck - cw) / jnp.abs(cw)))}


def case(mesh, spec, shape, wd):
    m, n = shape
    p, g, r, c = inputs(m, n)
    hp = {"lr": 1e-3, "beta": 0.999, "weight_decay": wd, "clip": 1.0}
    step = jnp.float32(3.0)
    rule = O.adalomo(backend="pallas", interpret=True, block=BLOCK)
    sh = NamedSharding(mesh, spec)

    @jax.jit
    def sharded(p, g, r, c):
        return rule.update(p, g, FactoredState(r, c, None), hp, step,
                           sharding=sh)

    with jax.set_mesh(mesh):
        new_p, st = sharded(jax.device_put(p, sh), jax.device_put(g, sh),
                            r, c)
    got = (new_p, st.r, st.c)
    ref = adalomo_update_ref(p, g, r, c, lr=1e-3, step=3.0, weight_decay=wd)
    whole_p, whole_s = update_tensor(
        p, g, FactoredState(r, c, None), lr=jnp.float32(1e-3), step=step,
        beta=0.999, weight_decay=wd, clip=1.0, cfg=AdaLomoConfig())
    one = adalomo_update(p, g, r, c, 1e-3, step, 0.999, wd, 1.0,
                         interpret=True, block=BLOCK)
    return {"ref": gaps(got, ref),
            "update_tensor": gaps(got, (whole_p, whole_s.r, whole_s.c)),
            "one_device": gaps(got, one),
            "param_spec": str(new_p.sharding.spec),
            "r_spec": str(st.r.sharding.spec),
            "c_spec": str(st.c.sharding.spec)}


def stacked_case(mesh, spec):
    """Four [128, 256] expert tensors as one [4, 128, 256] leaf: with the
    experts' spec (experts over ``model``, rows over ``data``) each device
    maps the kernel over its own experts' shards; with no sharding (None)
    the mesh in context runs it whole on every device.  Against ref.py
    per expert."""
    E, m, n = 4, 128, 256
    k = jax.random.split(jax.random.PRNGKey(E * m + n), 4)
    p = jax.random.normal(k[0], (E, m, n), jnp.float32) * 0.1
    g = jax.random.normal(k[1], (E, m, n), jnp.float32) * 0.3
    r = jax.random.uniform(k[2], (E, m), jnp.float32) * 1e-2
    c = jax.random.uniform(k[3], (E, n), jnp.float32) * 1e-2
    hp = {"lr": 1e-3, "beta": 0.999, "weight_decay": 0.0, "clip": 1.0}
    step = jnp.float32(3.0)
    rule = O.adalomo(backend="pallas", interpret=True, block=BLOCK)
    sh = None if spec is None else NamedSharding(mesh, spec)
    place = NamedSharding(mesh, P()) if sh is None else sh

    @jax.jit
    def update(p, g, r, c):
        return rule.update(p, g, FactoredState(r, c, None), hp, step,
                           sharding=sh)

    with jax.set_mesh(mesh):
        new_p, st = update(jax.device_put(p, place), jax.device_put(g, place),
                           r, c)
    ref = [adalomo_update_ref(p[e], g[e], r[e], c[e], lr=1e-3, step=3.0)
           for e in range(E)]
    return {"ref": gaps((new_p, st.r, st.c),
                        tuple(jnp.stack(x) for x in zip(*ref))),
            "param_spec": str(new_p.sharding.spec),
            "r_spec": str(st.r.sharding.spec),
            "c_spec": str(st.c.sharding.spec)}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    out = {}
    for s_name, spec in SPECS.items():
        for sh_name, shape in SHAPES.items():
            for wd_name, wd in WEIGHT_DECAY.items():
                out[f"{s_name}-{sh_name}-{wd_name}"] = case(mesh, spec,
                                                            shape, wd)
    out["experts"] = stacked_case(mesh, P("model", "data", None))
    out["no_sharding"] = stacked_case(mesh, None)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
