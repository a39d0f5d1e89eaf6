"""Seeded weights for a dense decoder, made on the device.

The benchmark makes the weights, not the program: the program is handed
them, and the reference makes the same values again from the same seed
after the program's state is freed.  Every leaf, and every layer of a
stacked leaf, draws from its own key, so one layer can be made again on
its own and equals the slice the whole-model call made.

The tree is the layout the program's dense decoder takes
(``{"outer", "shared", "stacks": {"blocks"}}``, layers stacked on axis 0,
matrices ``[in, out]``, RMSNorm weights stored as ``weight - 1``).  A
configuration with ``"qk_norm": true`` adds the per-head q and k norms of
Qwen3 to each block, after the nine leaves every dense decoder has, so
the keys of those nine stay the same.
"""
from __future__ import annotations

from functools import partial

from bench.harness import prng_key

# (path, kind): kind picks the init; the order fixes each leaf's key
OUTER = (("tok_embed", "embed"), ("final_norm.scale", "norm"),
         ("head", "head"))
BLOCK = (("ln1.scale", "norm"), ("ln2.scale", "norm"),
         ("attn.wq", "in"), ("attn.wk", "in"), ("attn.wv", "in"),
         ("attn.wo", "out"), ("mlp.w_gate", "in"), ("mlp.w_up", "in"),
         ("mlp.w_down", "out"))
QK_NORM = (("attn.q_norm.scale", "norm"), ("attn.k_norm.scale", "norm"))


class Static(dict):
    """A configuration usable as a static argument of ``jax.jit``."""

    def __init__(self, cfg):
        super().__init__({k: v for k, v in cfg.items()
                          if isinstance(v, (int, float, str, bool))})

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": H,
            "K": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim", d // H), "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"]}


def block_leaves(cfg: dict) -> tuple:
    """(path, kind) of each block leaf of this configuration, in key
    order."""
    return BLOCK + (QK_NORM if cfg.get("qk_norm") else ())


def leaf_shape(cfg: dict, path: str) -> tuple:
    m = dims(cfg)
    d, f, V = m["d"], m["f"], m["V"]
    qd, kd = m["H"] * m["dh"], m["K"] * m["dh"]
    return {"tok_embed": (V, d), "final_norm.scale": (d,), "head": (d, V),
            "ln1.scale": (d,), "ln2.scale": (d,), "attn.wq": (d, qd),
            "attn.wk": (d, kd), "attn.wv": (d, kd), "attn.wo": (qd, d),
            "attn.q_norm.scale": (m["dh"],), "attn.k_norm.scale": (m["dh"],),
            "mlp.w_gate": (d, f), "mlp.w_up": (d, f),
            "mlp.w_down": (f, d)}[path]


def served_dtype(cfg: dict):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]


def _draw(key, shape, kind, cfg):
    """One tensor: N(0, std²) drawn in float32, stored in the served type
    (norm weights stay float32, as the program keeps them)."""
    import jax
    import jax.numpy as jnp
    m = dims(cfg)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":       # weight = 1 + N(0, 0.1²): the norm does work
        return z * 0.1
    std = {"embed": 0.02, "head": m["d"] ** -0.5, "in": shape[0] ** -0.5,
           "out": shape[0] ** -0.5 * (2 * m["L"]) ** -0.5}[kind]
    return (z * std).astype(served_dtype(cfg))


def _outer_leaf(cfg, i, key):
    import jax
    path, kind = OUTER[i]
    return _draw(jax.random.fold_in(key, i), leaf_shape(cfg, path), kind, cfg)


def _layer_leaf(cfg, j, key, layer):
    import jax
    path, kind = block_leaves(cfg)[j]
    k = jax.random.fold_in(jax.random.fold_in(key, len(OUTER) + j), layer)
    return _draw(k, leaf_shape(cfg, path), kind, cfg)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split(".")
        node = out
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def _make(cfg, key):
    import jax
    import jax.numpy as jnp
    outer = {p: _outer_leaf(cfg, i, key) for i, (p, _) in enumerate(OUTER)}
    layers = jnp.arange(dims(cfg)["L"])
    blocks = {p: jax.vmap(partial(_layer_leaf, cfg, j, key))(layers)
              for j, (p, _) in enumerate(block_leaves(cfg))}
    return {"outer": _nest(outer), "shared": {},
            "stacks": {"blocks": _nest(blocks)}}


def init_params(cfg: dict, seed: int, shardings=None):
    """The whole model, in one jitted call: on the default device, or
    straight into ``shardings`` (a tree of the program's parameter
    shardings), so that no device holds more than its share."""
    import jax
    placed = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(_make, static_argnums=0, **placed)(Static(cfg),
                                                      prng_key(seed))


def outer_leaf(cfg: dict, seed: int, path: str):
    """One outer leaf, equal to the one :func:`init_params` made."""
    import jax
    i = [p for p, _ in OUTER].index(path)
    return jax.jit(_outer_leaf, static_argnums=(0, 1))(
        Static(cfg), i, prng_key(seed))


def layer_leaf(cfg: dict, seed: int, path: str, layer: int):
    """One layer's slice of a block leaf, equal to that slice of
    :func:`init_params`."""
    import jax
    j = [p for p, _ in block_leaves(cfg)].index(path)
    return jax.jit(_layer_leaf, static_argnums=(0, 1))(
        Static(cfg), j, prng_key(seed), layer)


def layer(cfg: dict, seed: int, index: int) -> dict:
    """Every block leaf of one layer, as a flat ``{path: array}``."""
    return {p: layer_leaf(cfg, seed, p, index) for p, _ in block_leaves(cfg)}
