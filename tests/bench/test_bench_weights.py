"""The benchmark's weights: made in one call from the seed, and made
again leaf by leaf (or layer by layer) to the same values, as the
reference and the check need; for danube's nine block leaves and for
Qwen3's eleven (q and k norms after the nine, so danube's keys stay)."""
import hashlib

import jax
import numpy as np
import pytest

from _tiny import CONFIGS, TINY
from bench import weights as W


@pytest.mark.parametrize("tiny", CONFIGS.values(), ids=CONFIGS)
def test_leaves_made_again_equal_the_whole_model(tiny):
    cfg = dict(tiny, torch_dtype="bfloat16")
    seed = 2 ** 36 + 11
    p = W.init_params(cfg, seed)
    for path, _ in W.OUTER:
        node = p["outer"]
        for k in path.split("."):
            node = node[k]
        np.testing.assert_array_equal(np.asarray(node, np.float32),
                                      np.asarray(W.outer_leaf(cfg, seed,
                                                              path),
                                                 np.float32))
    blocks = p["stacks"]["blocks"]
    for path, _ in W.block_leaves(cfg):
        node = blocks
        for k in path.split("."):
            node = node[k]
        for layer in range(cfg["num_hidden_layers"]):
            np.testing.assert_array_equal(
                np.asarray(node[layer], np.float32),
                np.asarray(W.layer_leaf(cfg, seed, path, layer),
                           np.float32))


@pytest.mark.parametrize("tiny", CONFIGS.values(), ids=CONFIGS)
def test_layout_and_types_are_the_programs(tiny):
    from repro.models.registry import get_arch
    cfg = dict(tiny, torch_dtype="float32")
    mine = jax.eval_shape(lambda: W.init_params(cfg, 1))
    prog = jax.eval_shape(lambda: get_arch(
        cfg["registry_id"], smoke=True).init_params(jax.random.PRNGKey(0)))
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seeds_differ():
    cfg = dict(TINY, torch_dtype="bfloat16")
    a = W.outer_leaf(cfg, 1, "head")
    b = W.outer_leaf(cfg, 2 ** 32 + 1, "head")
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_danube_weights_are_bitwise_those_of_before_qk_norm():
    # digest of the tiny danube model's weights, taken before the table
    # of block leaves came from the configuration
    p = W.init_params(dict(TINY, torch_dtype="bfloat16"), 2 ** 36 + 11)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == ("a701f66cc67bc2a117410ccd57d3a480"
                             "1fd7cc848f604e3e0fe0e813b5da7088")
