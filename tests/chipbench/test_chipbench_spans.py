"""The serving program's own spans, device scopes and build counter, read
through ``chipbench/spans.py``: a traced scheduler run at the test size
(``data/``), the build counter against the benchmark's warm-up, and
hand-made traces for each reduction."""

import glob
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import run, warmup  # noqa: E402
from chipbench import spans as sp  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.lookup import HERE as CB, Lookup  # noqa: E402
from repro.serve import SERVE_SPANS, BatchScheduler, Request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
CPU_TRACE = os.path.join(HERE, "cpu_trace.xplane.pb")
FAM = Lookup([DATA, CB], BENCH).family("qwen2")


@pytest.fixture(scope="module")
def tiny():
    cfg = Lookup([DATA], BENCH).config("tiny")
    return cfg, FAM.make_weights(cfg, 5)


class Hook:
    """The scheduler's per-segment callback, counting its calls."""

    def __init__(self):
        self.ticks = 0

    def tick(self, sched, segment):
        self.ticks += 1


def requests(vocab):
    """Six requests; the odd ones share a 40-token prefix (two and a half
    pages), so an admission after the first hits it and forks a page."""
    rng = np.random.default_rng(3)
    shared = rng.integers(1, vocab, 40).tolist()
    out = []
    for rid in range(6):
        prompt = (shared + rng.integers(1, vocab, 8).tolist() if rid % 2
                  else rng.integers(1, vocab, 24 + 8 * rid).tolist())
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=5 + rid))
    return out


def serve(eng, chaos=None):
    sched = BatchScheduler(eng, chaos=chaos)
    for r in requests(eng.lm.cfg.vocab):
        sched.submit(r)
    sched.run()
    return sched


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """One scheduler run and one ``generate()`` under the profiler, on a
    fresh engine: (engine, scheduler, the program's spans and ops)."""
    eng = FAM.engine(*tiny)
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        sched = serve(eng, Hook())
        eng.generate([[1, 2, 3]], max_new_tokens=2)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return eng, sched, sp.load(path[0])


def test_traced_run_records_every_serve_span(traced):
    _eng, sched, prog = traced
    assert {n for n, *_ in prog.spans} == set(SERVE_SPANS)
    assert sched.metrics["cow_copies"] >= 1
    assert sched.chaos.ticks == sched.metrics["segments"]
    fetches = [a for n, _, _, a in prog.spans if n == "serve.fetch"]
    assert [a["seg"] for a in fetches] == list(range(len(fetches)))


def test_admit_and_prefill_share_the_request_id(traced):
    _eng, _sched, prog = traced
    admits = {a["rid"]: (s, e, a) for n, s, e, a in prog.spans
              if n == "serve.admit"}
    prefills = {a["rid"]: (s, e, a) for n, s, e, a in prog.spans
                if n == "serve.prefill" and "rid" in a}
    assert set(admits) == set(prefills) == set(range(6))
    for rid, (s, e, a) in admits.items():
        ps, pe, pa = prefills[rid]
        assert s <= ps and pe <= e
        assert pa["tokens"] == a["prompt"] - a["prefix"]
    assert any(a["prefix"] > 0 for _, _, a in admits.values())


def test_builds_are_counted_and_spanned(traced):
    eng, sched, prog = traced
    runs = [(s, e) for n, s, e, _ in prog.spans if n == "serve.run"]
    inside = [b for b in sp.builds(prog, *runs[0])]
    assert sched.metrics["programs_built"] == len(inside) > 0
    assert {b["program"] for b in inside} == {"prefill", "segment",
                                              "cow_copy"}
    assert eng.programs_built == len(sp.builds(prog, -1e30, 1e30))


def test_tokens_are_the_same_without_the_profiler(tiny, traced):
    _eng, sched, _prog = traced
    again = serve(FAM.engine(*tiny))
    assert again.metrics["programs_built"] == sched.metrics["programs_built"]
    assert {r: q.generated for r, q in again.completed.items()} == \
        {r: q.generated for r, q in sched.completed.items()}


def test_build_counter_reads_zero_after_warm_up_and_one_after_a_new_length(
        tiny):
    cfg, _params = tiny
    eng = FAM.engine(*tiny)
    warmup.warm(eng, {"plain": [32, 48], "suffix": []}, 64, seed=1)
    rng = np.random.default_rng(0)
    sched = BatchScheduler(eng)
    for rid, n in enumerate([32, 48, 32]):
        sched.submit(Request(rid=rid, max_new_tokens=4, prompt=rng.integers(
            1, cfg["vocab_size"], n).tolist()))
    sched.run()
    assert sched.metrics["programs_built"] == 0
    sched.submit(Request(rid=9, max_new_tokens=4, prompt=rng.integers(
        1, cfg["vocab_size"], 40).tolist()))
    sched.run()
    assert sched.metrics["programs_built"] == 1


def test_traced_cell_reports_program_builds(capsys, tmp_path, monkeypatch):
    """The cell's traced run reads the counter through its reader, and
    the warm-up leaves nothing to build."""
    from chipbench import work
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setitem(work.PEAKS, "cpu", {"bf16_flops": 1e12,
                                            "hbm_bytes_s": 1e11,
                                            "hbm_bytes": 1e10})
    with open(BENCH) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "program_builds_in_run", "unit": "programs",
        "better": "lower", "source": "program_counter",
        "layer": "jit programs", "moves": "ttft_p95_ms",
        "workloads": ["tiny.chat"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    rc = run.main(["--workload", "tiny.chat", "--seed", "11", "--seconds",
                   "3", "--trace", "1"], roots=[DATA, CB],
                  benchmark=str(path), require_tpu=False,
                  out_dir=str(tmp_path / "trace"))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["program_builds_in_run"] == {"value": 0,
                                                      "unit": "programs"}
    prog = sp.load(glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                             recursive=True)[0])
    t = tr.load(glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                          recursive=True)[0])
    assert sp.builds(prog, *t.window()) == []
    assert sp.boundary_host_ms(t, prog) > 0


def test_the_reader_reads_nothing_from_a_program_without_the_counter():
    reader = Lookup().reader("program_builds_in_run")
    rec = run.Record(sched={"segments": 3})
    assert reader(rec) is None
    assert reader(run.Record(sched={"programs_built": 2})) == 2


# ------------------------------------------------------ hand-made traces
def test_attribute_charges_only_the_innermost_span():
    spans = [("run", 0.0, 10.0), ("serve.run", 0.5, 9.5, {}),
             ("admission", 1.0, 3.0), ("serve.pool", 1.5, 2.5, {}),
             ("serve.retire", 4.0, 5.0, {}), ("trace_open", 0.0, 0.0)]
    got = sp.attribute(spans, [(0.0, 2.0), (3.5, 6.0)])
    assert got == pytest.approx({"run": 0.5, "serve.run": 2.0,
                                 "admission": 0.5, "serve.pool": 0.5,
                                 "serve.retire": 1.0})
    assert sum(got.values()) == pytest.approx(4.5)
    assert sp.attribute([], [(1.0, 2.0)]) == {"untraced": 1.0}


def hand_program():
    """Window 0-10 s.  Two decode segments (1-4, 6-9 s): in each, a
    ``while`` holding the kernel under ``attention``, a pool slice under
    ``kv_cache``, an XLA copy of the scan under ``layers`` alone, and the
    MLP; a prefill op under ``kv_cache`` outside both segments."""
    base = "jit(seg)/while/body/layers"
    ops = []
    for t0 in (1.0, 6.0):
        ops += [("%while.1 = while(x)", t0, t0 + 3.0, f"{base[:-7]}"),
                ("%paged_decode_attention_grouped.7 = custom-call(q)",
                 t0, t0 + 1.0, f"{base}/attention"),
                ("%constant_dynamic-slice_fusion.7 = fusion(p)", t0 + 1.0,
                 t0 + 1.5, f"{base}/kv_cache/dynamic_slice"),
                ("%copy.94 = copy(p)", t0 + 1.5, t0 + 1.9,
                 f"{base}/while"),
                ("%fusion.3 = fusion(h)", t0 + 1.9, t0 + 2.8,
                 f"{base}/mlp/dot_general")]
    ops.append(("%scatter.2 = scatter(p)", 4.5, 5.0,
                "jit(_paged_slot_prefill_impl)/layers/attention/kv_cache"))
    t = tr.Trace(ops=[o[:3] + (0,) for o in ops],
                 modules=[("jit_seg(1)", 1.0, 4.0, 0),
                          ("jit_seg(1)", 6.0, 9.0, 0),
                          ("jit__paged_slot_prefill_impl", 4.5, 5.0, 0)],
                 spans=[("trace_open", 0.0, 0.0), ("trace_close", 10.0,
                                                    10.0)], devices=1)
    spans = [("serve.run", 0.0, 10.0, {}),
             ("serve.segment", 0.5, 0.6, {"seg": 4}),
             ("serve.fetch", 0.6, 4.2, {"seg": 4}),
             ("serve.retire", 4.2, 4.3, {"finished": 1}),
             ("serve.segment", 5.2, 5.3, {"seg": 5}),
             ("serve.fetch", 5.3, 9.1, {"seg": 5}),
             ("serve.segment", 9.4, 9.5, {"seg": 6})]
    return t, sp.Program(spans=spans, ops=ops)


def test_kv_move_share_counts_pool_movement_in_the_decode_program():
    t, prog = hand_program()
    # per segment: 0.5 s under kv_cache + 0.4 s under layers alone, of 3 s
    assert sp.kv_move_share(t, prog) == pytest.approx(30.0)
    assert sp.moves_kv("jit(seg)/layers/attention/kv_cache/scatter")
    assert not sp.moves_kv("jit(seg)/layers/attention/custom-call")
    assert not sp.moves_kv("jit(seg)/head/argmax")
    top = sp.kv_ops(t, prog, 3)
    assert [r[0] for r in top] == ["paged_decode_attention_grouped.7",
                                   "fusion.3",
                                   "constant_dynamic-slice_fusion.7"]
    assert [r[3] for r in top] == [False, False, True]


def test_kv_move_share_reads_nothing_without_scopes():
    t, prog = hand_program()
    bare = sp.Program(spans=prog.spans, ops=[o[:3] + ("",) for o in prog.ops])
    assert sp.kv_move_share(t, bare) is None


def test_boundary_host_ms_pairs_a_fetch_with_the_next_segment():
    t, prog = hand_program()
    # 4.2 -> 5.2 and 9.1 -> 9.4
    assert sp.boundary_gaps(prog, *t.window()) == pytest.approx([1.0, 0.3])
    assert sp.boundary_host_ms(t, prog) == pytest.approx(650.0)
    # a segment in another run() call is not the next one
    prog.spans[0] = ("serve.run", 0.0, 4.5, {})
    prog.spans.append(("serve.run", 5.0, 10.0, {}))
    assert sp.boundary_host_ms(t, prog) == pytest.approx(300.0)


def test_recorded_cpu_trace_loads_the_same_operations():
    """The loader reads the operations ``trace.load`` reads, so every
    reduction of ``trace.py`` gives the same numbers on them; a trace of
    a program without spans or scopes reads nothing."""
    t = tr.load(CPU_TRACE)
    prog = sp.load(CPU_TRACE)
    assert sorted(o[:3] + (0,) for o in prog.ops) == sorted(t.ops)
    again = tr.Trace(ops=[o[:3] + (0,) for o in prog.ops], modules=t.modules,
                     spans=t.spans, devices=t.devices)
    assert tr.busy(again) == tr.busy(t)
    assert tr.self_seconds(again) == tr.self_seconds(t)
    assert tr.attribute(again, tr.idle_gaps(again)) == \
        tr.attribute(t, tr.idle_gaps(t))
    assert sp.attribute(t.spans, tr.idle_gaps(t)) == pytest.approx(
        tr.attribute(t, tr.idle_gaps(t)))
    assert prog.spans == []
    assert sp.kv_move_share(t, prog) is None
    assert sp.boundary_host_ms(t, prog) is None


def test_device_ops_take_the_scope_from_the_event_metadata():
    """On a TPU the scope path is the ``tf_op`` stat of each ``XLA Ops``
    event's metadata, which ``ProfileData`` does not give: a hand-made
    ``XSpace`` with one scoped and one unscoped operation."""
    pb2 = sp._xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane.proto module in this installation")
    space = pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[7].name = "tf_op"
    plane.event_metadata[1].name = "%copy.92 = bf16[1,8,16,2,64] copy(p)"
    plane.event_metadata[1].stats.add(
        metadata_id=7, str_value="jit(seg)/layers/kv_cache/dynamic_slice:"
                                 "dynamic_slice")
    plane.event_metadata[2].name = "%copy.76 = bf16[24,8,16,2,64] copy(q)"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=2000, duration_ps=5000)
    line.events.add(metadata_id=2, offset_ps=9000, duration_ps=1000)
    plane.lines.add(name="XLA Modules")
    ops = sp.device_ops(space.SerializeToString())
    assert [(o[0].split(" = ")[0], o[3]) for o in ops] == [
        ("%copy.92", "jit(seg)/layers/kv_cache/dynamic_slice"),
        ("%copy.76", "")]
    assert ops[0][1] == pytest.approx(1e-6 + 2e-9)
    assert ops[0][2] - ops[0][1] == pytest.approx(5e-9)
    assert sp.moves_kv(ops[0][3]) and not sp.moves_kv(ops[1][3])
