"""The yardstick's counts against hand counts on a small configuration:
model FLOPs per token and the kernels' algorithm work."""
import numpy as np
import pytest

from bench import counters, flops, harness

SMALL = {"num_hidden_layers": 1, "hidden_size": 4, "intermediate_size": 8,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "vocab_size": 10, "sliding_window": None, "torch_dtype": "bfloat16"}


def test_forward_flops_by_hand():
    # per layer: wq 4x4, wk 4x2, wv 4x2, wo 4x4, gate/up 4x8, down 8x4
    assert flops.matmul_params(SMALL) == 16 + 8 + 8 + 16 + 32 + 32 + 32
    assert flops.head_params(SMALL) == 40
    # one document of 3 tokens: 2*144*3 matmul + 2*40*3 head + attention
    # 4 * H * dh * (1 + 2 + 3 keys) * L = 4*2*2*6
    assert flops.forward_flops(SMALL, [3]) == 864 + 240 + 96
    assert flops.train_flops(SMALL, {"tokens": np.zeros((1, 3))}) == 3 * 1200
    # prefill: logits at the last position only
    assert flops.prefill_flops(SMALL, 3) == 864 + 2 * 40 + 96


def test_window_and_documents():
    assert flops.visible_keys(5, None) == 15
    assert flops.visible_keys(5, 2) == 1 + 2 + 2 + 2 + 2
    assert flops.visible_keys(4, 4096) == 10
    # documents add up: a batch of rows is the sum of its rows
    assert flops.forward_flops(SMALL, [2, 3]) == (
        flops.forward_flops(SMALL, [2]) + flops.forward_flops(SMALL, [3]))
    assert flops.train_flops(SMALL, {"tokens": np.zeros((2, 3))}) == (
        6 * flops.forward_flops(SMALL, [3]))


def test_danube_1_8b_training_flops_per_token():
    cfg = harness.config("h2o-danube-1.8b")
    per_token = flops.train_flops(
        cfg, {"tokens": np.zeros((1, 4096))}) / 4096
    # 6 * 1.749e9 matmul+head weights, plus causal attention at S=4096
    assert per_token == pytest.approx(12.0e9, rel=0.01)


def test_decode_step_flops_by_hand():
    # two live rows with 3 and 5 cached tokens
    assert flops.decode_step_flops(SMALL, [3, 5]) == (
        2 * (144 + 40) * 2 + 4 * 2 * 2 * (3 + 5))


@pytest.mark.parametrize("qk_norm", [False, True])
def test_adalomo_work_by_hand(qk_norm):
    # 2-D tensors: tok_embed 10x4, head 4x10, and per layer wq 4x4,
    # wk 4x2, wv 4x2, wo 4x4, gate 4x8, up 4x8, down 8x4; Qwen3's q and
    # k norms are 1-D and add none
    shapes = [(10, 4), (4, 10), (4, 4), (4, 2), (4, 2), (4, 4), (4, 8),
              (4, 8), (8, 4)]
    small = dict(SMALL, qk_norm=qk_norm)
    assert sorted(counters.factored_shapes(small)) == sorted(shapes)
    elems = sum(m * n for m, n in shapes)
    sides = sum(m + n for m, n in shapes)
    w = counters.adalomo_step_work(small)
    # θ read, g read, θ written once in bf16; r and c read and written
    assert w["bytes"] == 3 * 2 * elems + 2 * 4 * sides
    assert w["flops"] == counters.ADALOMO_OPS_PER_ELEMENT * elems


def test_paged_attention_work_by_hand():
    # rows with 1, 16 and 17 cached tokens, page 16: 1, 1 and 2 pages
    w = counters.paged_attention_work(SMALL, [1, 16, 17], 16)
    page = 2 * 1 * 16 * 2 * 2            # K and V, one kv head, bf16
    qo = 2 * 2 * 2 * 2                   # q and o, two heads
    assert w["bytes"] == (4 * page + 3 * qo) * 1
    assert w["flops"] == 4 * 2 * 2 * (1 + 16 + 17)


def test_roofline_bound_names_the_limit():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counters.roofline_seconds(100, 50, pk) == (5.0, "memory")
    assert counters.roofline_seconds(1000, 5, pk) == (10.0, "compute")


def test_peaks_table_is_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")
