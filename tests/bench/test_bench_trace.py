"""The trace reduction, on a small trace recorded on a TPU v5e: three
calls of one jitted program holding the AdaLomo update kernel, the paged
decode-attention kernel and a matmul, each call inside host spans
``probe.outer`` > ``probe.call`` with a 10 ms sleep after the call."""
from pathlib import Path

import pytest

from bench import tracefile
from bench.tracefile import Event, Trace

FIXTURE = Path(__file__).parent / "data" / "kernels.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return tracefile.load(FIXTURE, span_prefix="probe.")


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return sum(b - a for a, b in out)


def test_one_device_three_programs(tr):
    assert tr.n_devices == 1
    mods = tr.modules_named("jit_f")
    assert len(mods) == 3
    assert all(m.dur > 0 for m in mods)


def test_device_clock_moved_after_enqueue(tr):
    # every program starts after the host enqueued it, and ends before
    # the host's completion callback (both about 1.4 ms apart raw)
    mods = tr.modules_named("jit_f")
    enq = sorted(s.start for s in tr.spans if s.name == "probe.call")
    for m, e in zip(mods, enq):
        assert m.start >= e


def test_busy_is_the_union_of_ops(tr):
    busy = tr.busy_s()
    w0, w1 = tr.window
    iv = [(max(e.start, w0), min(e.end, w1)) for e in tr.ops[0]]
    assert busy == pytest.approx(_union(iv) * 1e-9, rel=1e-12)
    assert 0 < busy < tr.window_s
    # each call runs ~45 us of device work
    assert 3 * 30e-6 < busy < 3 * 60e-6


def test_kernel_time_by_name(tr):
    ada = tr.op_seconds(r"adalomo_update(\.\d+)?$")
    paged = tr.op_seconds("paged_decode_attention")
    by_hand = sum(e.dur for e in tr.ops[0]
                  if tracefile.op_name(e.name).startswith("adalomo_update"))
    assert ada == pytest.approx(by_hand * 1e-9)
    assert ada > 0 and paged > 0
    # stats + update kernel in each of the three calls
    assert sum(1 for e in tr.ops[0] if tracefile.op_kind(e.name)
               == "adalomo_update") == 6
    per_call = [tr.ops_in(m, "paged_decode_attention")
                for m in tr.modules_named("jit_f")]
    assert sum(per_call) == pytest.approx(paged)
    kinds = dict(tr.top_ops(20))
    assert "paged_decode_attention_pallas" in kinds


def test_idle_gaps_are_named_by_the_open_span(tr):
    gaps = tr.idle_gaps(0, 2)
    # the two sleeps between the three calls, inside probe.outer
    assert [name for name, _ in gaps] == ["probe.outer", "probe.outer"]
    assert all(10e-3 <= s < 15e-3 for _, s in gaps)
    bd = tr.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduction_on_hand_made_events():
    ops = [Event("%a.1 = x", 0, 10), Event("%b.2 = y", 5, 10),
           Event("%a.3 = z", 40, 10)]
    spans = [Event("bench.outer", 0, 100), Event("bench.inner", 20, 10),
             Event(tracefile.WINDOW_SPAN, 0, 60)]
    t = Trace(ops=[ops], modules=[[Event("jit_s", 0, 50)]], spans=spans,
              window=(0, 60))
    assert t.busy_s() == pytest.approx(25e-9)
    assert t.op_seconds(r"a(\.\d+)?$") == pytest.approx(20e-9)
    assert t.idle_gaps(0, 5) == [("bench.inner", pytest.approx(25e-9)),
                                 ("bench.outer", pytest.approx(10e-9))]
    assert t.top_ops(5)[0] == ("a", pytest.approx(20e-9))


def _four_device_step(kernel_ns, other_ns=1000.0):
    """One ``jit_one_step`` run on each of four devices: an op of the
    forward's attention, then the AdaLomo kernel under ``update``."""
    from bench import program_trace
    ops, mods, scopes = [], [], []
    for _ in range(4):
        ops.append([Event("%fusion.1 = f", 10, other_ns),
                    Event("%adalomo_update.2 = k", 20 + other_ns,
                          kernel_ns)])
        scopes.append(["jit(one_step)/fwd/attention/dot_general",
                       "jit(one_step)/bwd/update/pallas_call"])
        mods.append([Event("jit_one_step", 0, 40 + other_ns + kernel_ns)])
    end = 50 + other_ns + kernel_ns
    t = Trace(ops=ops, modules=mods,
              spans=[Event(tracefile.WINDOW_SPAN, 0, end)], window=(0, end))
    program_trace.ProgramTrace(trace=t, spans=[], scopes=scopes)
    return t


@pytest.mark.parametrize("sharded", [True, False])
def test_training_readers_on_a_four_device_trace(sharded):
    import numpy as np

    from _tiny import TINY
    from bench import counters, flops, harness
    cfg = dict(TINY, torch_dtype="bfloat16")
    work = counters.adalomo_step_work(cfg)
    # the kernel's bytes take 1 ms on one chip at this peak
    peaks = {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": work["bytes"] / 1e-3}
    kernel_ns = 1e6 / 4 if sharded else 1e6
    t = _four_device_step(kernel_ns)
    batch = {"tokens": np.zeros((4, 32))}
    f = flops.train_flops(cfg, batch)
    secs = f / (4 * peaks["bf16_flops_per_s"])      # four chips at peak
    rec = {"kind": "train", "cfg": cfg, "peaks": peaks,
           "window": {"steps": 1, "flops": f, "seconds": secs}}
    read = {m: harness.metric_reader(m)(t, rec) for m in (
        "adalomo_update_roofline", "mfu.train", "update_share.train",
        "idle_share.train", "attention_share.train",
        "fused_update_share.train")}
    # sharded over the four chips at its roofline: 100%; every chip on
    # the whole tensors: a quarter
    assert read["adalomo_update_roofline"] == pytest.approx(
        100.0 if sharded else 25.0)
    assert read["mfu.train"] == pytest.approx(100.0)
    for name, v in read.items():
        assert v is not None and 0 <= v <= 100 + 1e-9, (name, v)
