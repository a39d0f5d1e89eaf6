"""The traffic generator: the same seed gives the same mix, every seed the
same schedule of sizes and arrivals with other tokens, and lengths stay
inside their clips."""
import numpy as np
import pytest

from bench import gen, harness

V = 32000


@pytest.fixture(scope="module")
def chat():
    return harness.traffic("chat")


def test_train_rows_repeat_per_seed_and_differ_per_row():
    spec = harness.traffic("seq4k")
    spec = dict(spec, seq_len=64, batch=4)
    a = gen.TrainTraffic(spec, V, 2 ** 33 + 5).batch(3)
    b = gen.TrainTraffic(spec, V, 2 ** 33 + 5).batch(3)
    c = gen.TrainTraffic(spec, V, 6).batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert len({r.tobytes() for r in a["tokens"]}) == 4
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < V


def test_arrivals_same_seed_same_mix(chat):
    a = gen.serve_arrivals(chat, V, 2 ** 40 + 3, 45)
    b = gen.serve_arrivals(chat, V, 2 ** 40 + 3, 45)
    assert [(x.due, x.prompt, x.max_new_tokens) for x in a] == \
        [(x.due, x.prompt, x.max_new_tokens) for x in b]


def test_arrivals_same_schedule_other_tokens(chat):
    a = gen.serve_arrivals(chat, V, 1, 45)
    b = gen.serve_arrivals(chat, V, 2 ** 31 + 9, 45)
    sched = lambda xs: [(x.due, len(x.prompt), x.max_new_tokens) for x in xs]
    assert sched(a) == sched(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    span = chat["lead_in_s"] + 45
    assert len(a) == round(chat["rate"] * span)
    assert a[0].due == 0.0 and a[-1].due < span
    assert len({len(x.prompt) for x in a}) > len(a) // 4


def test_lengths_inside_their_clips(chat):
    xs = gen.serve_arrivals(chat, V, 7, 45)
    p, o = chat["prompt"], chat["output"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in xs)
    assert all(o["min"] <= x.max_new_tokens <= o["max"] for x in xs)
    med = np.median([len(x.prompt) for x in xs])
    assert 0.6 * p["median"] < med < 1.5 * p["median"]
    e = chat["engine"]
    assert all(len(x.prompt) + x.max_new_tokens - 1
               <= e["max_pages_per_seq"] * e["page_size"] for x in xs)
