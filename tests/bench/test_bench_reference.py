"""The reference's loss, taken a block of tokens at a time where a row's
logits would pass ``LOGITS_BLOCK``, reads what whole rows read: the same
loss and the same gradients, to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import TINY
from bench import reference as R


def loss_and_grads(x, labels, final, head):
    def f(final_, head_, x_):
        s, n = R.loss_sums(TINY, R.DOTS["none"], final_, head_, x_, labels)
        return s / n
    return jax.value_and_grad(f, argnums=(0, 1, 2))(final, head, x)


@pytest.mark.parametrize("tokens", [1, 8])
def test_loss_in_blocks_of_tokens_reads_as_whole_rows(monkeypatch, tokens):
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    B, S, d, V = 2, 32, 16, 64
    x = jax.random.normal(k[0], (B, S, d))
    head = jax.random.normal(k[1], (d, V)) / 4
    final = jax.random.normal(k[2], (d,)) / 10
    labels = jax.random.randint(k[3], (B, S), -1, V)   # -1: no label
    whole = loss_and_grads(x, labels, final, head)
    monkeypatch.setattr(R, "LOGITS_BLOCK", tokens * V)
    blocks = loss_and_grads(x, labels, final, head)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocks)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-6,
                                   atol=1e-7)
    assert whole[0] > 0 and jnp.isfinite(whole[0])
