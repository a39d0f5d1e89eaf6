"""``collective_exposed_share.train`` and the collective counts
(``bench/collectives.py``) on a hand-made four-chip training step with
collectives partly hidden under compute; the AdaLomo roofline of a
kernel sharded over the four chips; one-chip traces read nothing."""
from pathlib import Path

import numpy as np
import pytest

from bench import collectives, counters, harness, tracefile
from bench.tracefile import Event, Trace

FIXTURE = Path(__file__).parent / "data" / "kernels.xplane.pb"

# HLO texts of ops as the TPU's compiler names them (a v5e:2x2 compile of
# the mesh step), with what each counts as
HLO_KINDS = [
    ("%all-reduce.231 = (f32[4096]{0:T(1024)S(1)}, f32[]) all-reduce("
     "%a, %b)", "all-reduce"),
    ("%all-gather.110 = f32[2,5120]{1,0:T(2,128)} all-gather(%g), "
     "dimensions={1}", "all-gather"),
    ("%fusion.15 = f32[304000,8,128]{2,1,0:T(8,128)} fusion(%fusion.276), "
     "kind=kCustom, calls=%all-reduce-scatter", "reduce-scatter"),
    ("%reduce-scatter.3 = bf16[2560,512] reduce-scatter(%x)",
     "reduce-scatter"),
    ("%collective-permute-done = s32[1,4096,1] collective-permute-done("
     "%collective-permute-start)", "other"),
    ("%all-to-all.2 = bf16[2,4096,8,128] all-to-all(%y)", "other"),
    ("%async-collective-start = (f32[1,1,2560], f32[2,1,2560]) fusion("
     "%reshape.1133), kind=kCustom, calls=%fused_computation.577", "other"),
    # compute that carries a collective counts as compute
    ("%async_collective_fusion.12 = (bf16[12800,2560]) fusion(%p), "
     "kind=kCustom, calls=%async_collective_fusion.763", None),
    ("%fusion.9 = bf16[2560,512] fusion(%z), kind=kLoop, "
     "calls=%fused_computation.12", None),
    ("%copy-start.95 = (s32[1,4096]) copy-start(s32[1,4096] %c)", None),
    ("%adalomo_update.7 = bf16[2560,512] custom-call(%p, %g), "
     "custom_call_target=\"tpu_custom_call\"", None),
]


@pytest.mark.parametrize("text,kind", HLO_KINDS)
def test_collective_kind_from_the_op_text(text, kind):
    assert collectives.kind(text) == kind


def _chip(hidden: bool, kernel_ns: float):
    """One ``jit_one_step`` run of 900 ns + kernel_ns on one chip:

    compute      [0, 400)  (to 450 where ``hidden``)
    all-gather   [350, 450)           exposed 50 (0 where hidden)
    all-reduce   start 450, done [700, 720), compute [460, 700):
                                      exposed 10 + 20
    reduce-scatter fusion [720, 800)  exposed 80
    permute      start [800, 805), done [805, 810)    exposed 10
    async collective fusion [810, 900) (compute), the kernel after it
    """
    ops = [Event("%fusion.1 = f", 0, 450 if hidden else 400),
           Event("%all-gather.2 = ag", 350, 100),
           Event("%all-reduce-start.3 = ars", 450, 10),
           Event("%fusion.4 = f", 460, 240),
           Event("%all-reduce-done.3 = ard(%all-reduce-start.3)", 700, 20),
           Event("%fusion.5 = rs, kind=kCustom, calls=%all-reduce-scatter",
                 720, 80),
           Event("%collective-permute-start.1 = cps", 800, 5),
           Event("%collective-permute-done.1 = cpd", 805, 5),
           Event("%async_collective_fusion.6 = acf, kind=kCustom, "
                 "calls=%async_collective_fusion.9", 810, 90),
           Event("%adalomo_update.7 = k", 900, kernel_ns / 3),
           Event("%adalomo_update.8 = k", 900 + kernel_ns / 3, kernel_ns / 3),
           Event("%adalomo_update.9 = k", 900 + 2 * kernel_ns / 3,
                 kernel_ns / 3),
           Event("%while.10 = loop", 0, 900)]
    return ops, [Event("jit_one_step", 0, 900 + kernel_ns)]


def _four_chips(kernel_ns: float = 100.0):
    ops, mods = zip(*[_chip(d >= 2, kernel_ns) for d in range(4)])
    end = 1000 + kernel_ns
    return Trace(ops=list(ops), modules=list(mods),
                 spans=[Event(tracefile.WINDOW_SPAN, 0, end)],
                 window=(0, end))


def test_exposed_share_and_counts_on_four_chips():
    t = _four_chips()
    chips = collectives.per_chip(t)
    assert [c["device"] for c in chips] == [0, 1, 2, 3]
    for c in chips:
        assert c["steps"] == 1
        assert c["per_step"] == {"all-gather": 1, "all-reduce": 1,
                                 "reduce-scatter": 1, "other": 1}
        # all-gather 100 + all-reduce pair 270 + reduce-scatter 80 +
        # permute pair 10
        assert c["collective_s"] == pytest.approx(460e-9)
        assert c["busy_s"] == pytest.approx(1000e-9)
    # exposed: 50 + 30 + 80 + 10 = 170 of 1000 on chips 0-1; the
    # all-gather hidden under compute on chips 2-3: 120
    assert [c["exposed_s"] for c in chips] == pytest.approx(
        [170e-9, 170e-9, 120e-9, 120e-9])
    rec = {"kind": "train"}
    share = harness.metric_reader("collective_exposed_share.train")(t, rec)
    assert share == pytest.approx((17.0 + 17.0 + 12.0 + 12.0) / 4)
    assert 0 < share <= 100
    assert rec["collectives_per_step"][0]["reduce-scatter"] == 1


def test_sharded_kernel_roofline_is_at_most_100():
    from _tiny import TINY
    cfg = dict(TINY, torch_dtype="bfloat16")
    work = counters.adalomo_step_work(cfg)
    # the whole model's kernel bytes take 3 us on one chip at this peak:
    # a quarter of them on each of four chips, 750 ns, at the roofline
    peaks = {"bf16_flops_per_s": 1e30,
             "hbm_bytes_per_s": work["bytes"] / 3e-6}
    rec = {"kind": "train", "cfg": cfg, "peaks": peaks}
    read = harness.metric_reader("adalomo_update_roofline")
    assert read(_four_chips(750.0), rec) == pytest.approx(100.0)
    slower = read(_four_chips(1200.0), rec)
    assert slower == pytest.approx(62.5)


def test_one_chip_traces_read_nothing():
    read = harness.metric_reader("collective_exposed_share.train")
    t = _four_chips()
    one = Trace(ops=t.ops[:1], modules=t.modules[:1], spans=t.spans,
                window=t.window)
    assert read(one, {"kind": "train"}) is None
    recorded = tracefile.load(FIXTURE, span_prefix="probe.")
    assert recorded.n_devices == 1
    assert read(recorded, {"kind": "train"}) is None
    assert collectives.per_chip(recorded) == []
    assert read(t, {"kind": "serve"}) is None


def test_steps_are_averaged_per_chip():
    """Two step runs on each chip: the counts are per step."""
    ops, mods = [], []
    for d in range(2):
        o, m = _chip(False, 100.0)
        shifted = [Event(e.name, e.start + 2000, e.dur) for e in o]
        ops.append(o + shifted)
        mods.append(m + [Event(m[0].name, 2000, m[0].dur)])
    t = Trace(ops=ops, modules=mods, spans=[], window=(0, 4000))
    chips = collectives.per_chip(t)
    assert all(c["steps"] == 2 and c["per_step"]["all-gather"] == 1
               for c in chips)
    assert np.allclose([c["exposed_share"] for c in chips], 17.0)
