"""The benchmark's trace reduction, on hand-made traces and on a small
trace recorded on the CPU (``cpu_trace.xplane.pb``: two jitted programs
named like the serving programs, run twice under the benchmark's host
spans, with ``jax.profiler`` on the CPU backend)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import trace as tr  # noqa: E402
from chipbench.stats import merged, union_length  # noqa: E402

CPU_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "cpu_trace.xplane.pb")


def on(items, dev=0):
    """(name, start, end) items on device ``dev``."""
    return [(n, s, e, dev) for n, s, e in items]


def hand_trace():
    """Window 0-10 s.  Ops: 1-3 (segment program), 2-4 (overlapping op),
    6-7 (prefill program's kernel), 8-9 (segment).  Host: fetch over
    4-5, tick 5-5.5, prefill_slot 5.5-6, run 0-10."""
    ops = on([("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
              ("%flash_attention_bhsd.3 = bf16[1,14,64,64] custom-call(q)",
               6.0, 7.0),
              ("%paged_decode_attention_grouped.7 = bf16[8,7,128] "
               "custom-call(q)", 8.0, 9.0)])
    modules = on([("jit_seg(1)", 1.0, 4.0),
                  ("jit__paged_slot_prefill_impl", 6.0, 7.0),
                  ("jit_seg(1)", 8.0, 9.0)])
    spans = [("trace_open", 0.0, 0.0), ("run", 0.0, 10.0),
             ("fetch", 4.0, 5.0), ("tick", 5.0, 5.5),
             ("prefill_slot", 5.5, 6.0), ("trace_close", 10.0, 10.0)]
    return tr.Trace(ops=ops, modules=modules, spans=spans, devices=1)


def test_union_and_merge():
    assert union_length([(1, 3), (2, 4), (6, 7)]) == 4
    assert merged([(2, 4), (1, 3), (6, 7), (7, 8)]) == [[1, 4], [6, 8]]
    assert union_length([]) == 0


def test_busy_and_idle():
    t = hand_trace()
    assert t.window() == (0.0, 10.0)
    assert tr.busy(t) == pytest.approx(5.0)        # 1-4, 6-7, 8-9
    assert tr.idle_gaps(t) == [(0.0, 1.0), (4.0, 6.0), (7.0, 8.0),
                               (9.0, 10.0)]


def test_idle_gaps_go_to_the_innermost_host_span():
    t = hand_trace()
    got = tr.attribute(t, tr.idle_gaps(t))
    assert got == pytest.approx({"fetch": 1.0, "tick": 0.5,
                                 "prefill_slot": 0.5, "run": 3.0})
    assert sum(got.values()) == pytest.approx(5.0)


def test_idle_outside_every_span_is_untraced():
    t = hand_trace()
    t.spans = [s for s in t.spans if s[0] != "run"]
    got = tr.attribute(t, [(7.0, 8.0)])
    assert got == {"untraced": 1.0}


def test_program_and_kernel_time():
    t = hand_trace()
    assert tr.program_runs(t, "segment") == [(1.0, 4.0), (8.0, 9.0)]
    assert tr.program_seconds(t, "segment") == pytest.approx(4.0)
    assert tr.program_seconds(t, "prefill") == pytest.approx(1.0)
    assert tr.kernel_seconds(t, "pallas_flash") == pytest.approx(1.0)
    assert tr.kernel_seconds(t, "pallas_paged") == pytest.approx(1.0)
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                        ["c", 2.0]]
    t.ops.append(("%paged_decode_attention_grouped.7 = bf16[8] copy(q)",
                  9.0, 9.5, 0))         # not the kernel's custom call
    assert tr.kernel_seconds(t, "pallas_paged") == pytest.approx(1.0)


def test_self_time_leaves_out_nested_operations():
    t = tr.Trace(ops=on([("%while.1 = (s32[]) while(x)", 0.5, 3.5),
                         ("%fusion.2 = f32[] fusion(y)", 1.0, 2.0),
                         ("%copy.3 = f32[] copy(z)", 2.0, 3.0),
                         ("%copy.3 = f32[] copy(z)", 4.0, 4.5)]),
                 modules=[], spans=[("trace_open", 0.0, 0.0),
                                    ("trace_close", 5.0, 5.0)], devices=1)
    got = tr.self_seconds(t)
    assert got == pytest.approx({"while.1": 1.0, "fusion.2": 1.0,
                                 "copy.3": 1.5})
    assert sum(got.values()) == pytest.approx(tr.busy(t))


def test_devices_average():
    t = hand_trace()
    t.devices = 2
    assert tr.busy(t) == pytest.approx(2.5)
    assert tr.program_seconds(t, "segment") == pytest.approx(2.0)


def test_window_clips_what_lies_outside():
    t = hand_trace()
    t.ops.append(("fusion.9", 11.0, 12.0, 0))
    t.ops.append(("fusion.8", 9.5, 10.5, 0))
    assert tr.busy(t) == pytest.approx(5.5)
    assert tr.self_seconds(t)["fusion.8"] == pytest.approx(0.5)
    assert "fusion.9" not in tr.self_seconds(t)


def test_recorded_cpu_trace():
    t = tr.load(CPU_TRACE)
    names = [n for n, _, _ in t.spans]
    assert names.count("prefill_slot") == 2 and names.count("segment") == 2
    assert names.count("fetch") == 2 and names.count("idle_sleep") == 2
    lo, hi = t.window()
    assert lo < hi
    busy = tr.busy(t)
    assert 0 < busy < hi - lo
    gaps = tr.idle_gaps(t)
    assert union_length(gaps) == pytest.approx(hi - lo - busy)
    idle = tr.attribute(t, gaps)
    assert sum(idle.values()) == pytest.approx(hi - lo - busy)
    # the two sleeps are the longest idle stretch
    assert max(idle, key=idle.get) == "idle_sleep"
    assert idle["idle_sleep"] > 0.003
    # per-program device time: both programs ran twice
    assert len({round(s, 9) for s, _ in tr.program_runs(t, "prefill")}) >= 2
    assert tr.program_seconds(t, "segment") > 0
    assert tr.program_seconds(t, "prefill") > 0
    assert tr.program_seconds(t, "segment") + tr.program_seconds(
        t, "prefill") == pytest.approx(busy, rel=0.05)
    ops = tr.self_seconds(t)
    assert sum(ops.values()) == pytest.approx(busy, rel=1e-6)


def two_devices():
    """Window 0-10 s on two devices that run one decode segment together
    (a layer scan: ``while.1``), each busy all the way.  Device 0: a
    matmul 0-4, an all-reduce 4-5 with nothing else on the device, the
    next fusion 5-10 (whose operand is named after the all-reduce).
    Device 1: the same matmul, an all-reduce 4-6 under which a fusion
    runs from 4.5, and the next fusion 6-10.  Host: run 0-10, fetch
    6-10."""
    layer = [("%while.1 = (s32[]) while(x)", 0.0, 10.0),
             ("%fusion.1 = bf16[8,3584] fusion(a)", 0.0, 4.0)]
    ops = on(layer + [
        ("%all-reduce.2 = bf16[8,3584] all-reduce(%fusion.1)", 4.0, 5.0),
        ("%fusion.3 = bf16[8,3584] fusion(%all-reduce.2)", 5.0, 10.0)], 0)
    ops += on(layer + [
        ("%all-reduce.2 = bf16[8,3584] all-reduce(%fusion.1)", 4.0, 6.0),
        ("%fusion.5 = bf16[8,3584] fusion(b)", 4.5, 6.0),
        ("%fusion.6 = bf16[8,3584] fusion(%all-reduce.2)", 6.0, 10.0)], 1)
    modules = on([("jit_seg(1)", 0.0, 10.0)], 0) + \
        on([("jit_seg(1)", 0.0, 10.0)], 1)
    spans = [("trace_open", 0.0, 0.0), ("run", 0.0, 10.0),
             ("fetch", 6.0, 10.0), ("trace_close", 10.0, 10.0)]
    return tr.Trace(ops=ops, modules=modules, spans=spans, devices=2)


class _Tracer:
    on, off = 0.0, 10.0


def _rec(t):
    class Rec:
        trace, tracer = t, _Tracer()
    return Rec()


@pytest.mark.parametrize("stop,idle,attributed", [
    (10.0, 0.0, {}),
    # device 1 stops at 6 while device 0 works on: 4 of 20 device-seconds
    (6.0, 20.0, {"fetch": 2.0})], ids=["both_busy", "one_idles"])
def test_idle_is_per_device(stop, idle, attributed):
    """Two devices busy together read 0 % idle, not the 50 % a union over
    both planes divided by two would give; a device idle alone counts
    for its share."""
    from chipbench.lookup import Lookup
    t = two_devices()
    t.ops = [(n, s, min(e, stop) if d else e, d) for n, s, e, d in t.ops
             if not d or s < stop]
    assert tr.busy(t) == pytest.approx(10.0 - idle / 10.0)
    assert Lookup().reader("device_idle_share")(_rec(t)) == \
        pytest.approx(idle)
    assert tr.attribute(t, tr.idle_gaps(t)) == pytest.approx(attributed)
    assert sum(tr.self_seconds(t).values()) == pytest.approx(tr.busy(t))


def test_a_device_alone_is_one_plane():
    t = two_devices().on(1)
    assert t.devices == 1 and {o[3] for o in t.ops} == {0}
    assert len(t.ops) == 5
    assert tr.program_seconds(t, "segment") == pytest.approx(10.0)
    assert tr.self_seconds(t)["all-reduce.2"] == pytest.approx(0.5)


def test_collective_exposed_on_its_own_device():
    """A collective counts where it runs with nothing else on its own
    device: all of device 0's all-reduce (a fusion on device 1 at the
    same time does not hide it), half a second of device 1's; an operand
    named after a collective is no collective."""
    from chipbench.lookup import Lookup
    t = two_devices()
    assert tr.collective_seconds(t.on(0), "segment") == \
        pytest.approx((1.0, 10.0))
    assert tr.collective_seconds(t.on(1), "segment") == \
        pytest.approx((0.5, 10.0))
    read = Lookup().reader("collective_exposed_share")
    assert read(_rec(t)) == pytest.approx(7.5)
    assert read(_rec(hand_trace())) is None     # no collective: nothing


@pytest.mark.parametrize("text,collective", [
    ("%all-reduce.8 = bf16[128,1,3584]{2,0,1} all-reduce(%fusion.175), "
     "channel_id=2, replica_groups=[1,4]<=[4], to_apply=%add.1", True),
    ("%all-gather.7 = bf16[128,152064]{1,0} all-gather(%gte.1), "
     "dimensions={1}", True),
    ("%all-reduce-start.3 = bf16[8]{0} all-reduce-start(%f.1)", True),
    ("%all-reduce-done.3 = bf16[8]{0} all-reduce-done(%all-reduce-start.3)",
     True),
    ("%collective-permute-done.1 = f32[4] collective-permute-done(%c)",
     True),
    ("%reduce-scatter.2 = f32[4] reduce-scatter(%x)", True),
    ("%fusion.176 = (f32[128], bf16[128,1,3584]) fusion(%gte.1234, "
     "%all-reduce.8), kind=kLoop", False),
    ("%add.2089 = bf16[128,1,3584] add(%gte.1204, %all-reduce.9)", False),
    ("%paged_decode_attention_grouped.1 = bf16[128,8,128] custom-call(%q)",
     False)])
def test_collective_opcodes(text, collective):
    """Matched on the HLO opcode (as the compiler writes the v5e's decode
    segment on a (1, 4) mesh), not on operand names."""
    assert bool(tr.COLLECTIVE.search(text)) is collective
