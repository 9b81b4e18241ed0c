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


def hand_trace():
    """Window 0-10 s.  Ops: 1-3 (segment program), 2-4 (overlapping op),
    6-7 (prefill program's kernel), 8-9 (segment).  Host: fetch over
    4-5, tick 5-5.5, prefill_slot 5.5-6, run 0-10."""
    ops = [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
           ("%flash_attention_bhsd.3 = bf16[1,14,64,64] custom-call(q)",
            6.0, 7.0),
           ("%paged_decode_attention_grouped.7 = bf16[8,7,128] "
            "custom-call(q)", 8.0, 9.0)]
    modules = [("jit_seg(1)", 1.0, 4.0), ("jit__paged_slot_prefill_impl", 6.0,
                                          7.0), ("jit_seg(1)", 8.0, 9.0)]
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
                  9.0, 9.5))            # not the kernel's custom call
    assert tr.kernel_seconds(t, "pallas_paged") == pytest.approx(1.0)


def test_self_time_leaves_out_nested_operations():
    t = tr.Trace(ops=[("%while.1 = (s32[]) while(x)", 0.5, 3.5),
                      ("%fusion.2 = f32[] fusion(y)", 1.0, 2.0),
                      ("%copy.3 = f32[] copy(z)", 2.0, 3.0),
                      ("%copy.3 = f32[] copy(z)", 4.0, 4.5)],
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
    t.ops.append(("fusion.9", 11.0, 12.0))
    t.ops.append(("fusion.8", 9.5, 10.5))
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
