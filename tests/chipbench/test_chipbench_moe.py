"""The Qwen3-MoE family and its three readers: the weights it makes for
``qwen3-moe-235b-a22b`` (shapes and bytes, from shapes alone), its counts,
the readers on hand-made records, and a CPU-sized cell of the family
(``data/configs/tinymoe.json``: 128 wide, two layers, experts 4-7 of 16
held) run end to end, traced, and against its fp8 control."""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import control, run, work  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.lookup import HERE as CB, Lookup  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**33 + 17
CELL = "qwen3-moe-235b-a22b.chat"
METRICS = ("moe_gmm_roofline", "moe_segment_share", "moe_rows_per_expert_load")


@pytest.fixture(scope="module")
def lk():
    return Lookup()


@pytest.fixture(scope="module")
def fam(lk):
    return lk.family("qwen3_moe")


@pytest.fixture(scope="module")
def cfg(lk):
    return lk.config("qwen3-moe-235b-a22b")


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


# ------------------------------------------------ the configuration's size
def test_weights_are_one_chips_share(fam, cfg):
    """Six layers of 71.3 M attention, 0.5 M router and 604.0 M held-expert
    parameters, the embedding and the untied head whole: 10.60 GB in
    bf16, in the program's layout."""
    tree = jax.eval_shape(lambda: fam.make_weights(cfg, 1))
    blocks = tree["blocks"]
    assert blocks["moe"]["w_gate"].shape == (6, 32, 4096, 1536)
    assert blocks["moe"]["w_down"].shape == (6, 32, 1536, 4096)
    assert blocks["moe"]["router"].shape == (6, 4096, 128)
    assert blocks["attn"]["q_norm"]["scale"].shape == (6, 128)
    n = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa
    assert n(blocks["attn"]) == 6 * (71_303_168 + 2 * 128)
    assert n(blocks["moe"]) == 6 * (603_979_776 + 524_288)
    byts = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert byts == pytest.approx(10.60e9, rel=2e-3)
    # the program's own layout, as its engine will read it
    lm = fam._lm(cfg)
    assert jax.tree.structure(jax.eval_shape(lm.init, jax.random.PRNGKey(0))
                              ) == jax.tree.structure(tree)


def test_counts(fam, cfg):
    assert fam.kv_bytes_per_token(cfg) == 12_288
    assert fam.routed_experts(cfg) == 128
    # attention + router + 8 x 32/128 experts of 3 x 4096 x 1536
    assert fam.layer_matmul_params(cfg) == \
        71_303_168 + 524_288 + 2 * 18_874_368
    flops, byts = fam.gmm_work(cfg, held_rows=10, loads=2)
    assert flops == 6 * 4096 * 1536 * 10
    assert byts == 2 * 37_748_736 + 10 * (2 * (4096 * 2 + 1536 * 4)
                                          + 1536 * 2 + 4096 * 4)


# ------------------------------------------------------ hand-made records
class _Tracer:
    on, off = 0.0, 10.0


class _Drv:
    def __init__(self, segments, prefills):
        self.segments, self.prefills = segments, prefills


def _rec(lk, fam, cfg, sched, kernel_s, segments, prefills=(),
         seg_runs=((1.0, 9.0),)):
    name = "%grouped_expert_matmul.3 = f32[1024,1536] custom-call(x)"
    ops = [(name, 1.0, 1.0 + kernel_s / 2, 0),
           (name, 5.0, 5.0 + kernel_s / 2, 0)]
    trace = tr.Trace(ops=ops, modules=[("jit_seg(8)", s, e, 0)
                                       for s, e in seg_runs],
                     spans=[("trace_open", 0.0, 0.0),
                            ("trace_close", 10.0, 10.0)], devices=1)
    return run.Record(cfg=cfg, fam=fam, sched=sched, trace=trace,
                      tracer=_Tracer(), drv=_Drv(list(segments),
                                                 list(prefills)),
                      device_kind="TPU v5 lite", chips=1)


def test_gmm_roofline_is_100_when_the_kernel_takes_the_least_time(lk, fam,
                                                                 cfg):
    """Every held row hits one expert: each of the six layers of the
    traced segment's eight steps reads that expert once, and nothing
    else.  A kernel that takes exactly that read's time reads 100 %, not
    more; one twice as slow, 50 %."""
    rows, steps, layers = 40, 8, 6
    sched = {"moe_tokens.decode": layers * rows * steps,
             "moe_rows_held.decode": layers * rows * steps,
             "moe_expert_loads.decode": layers * steps,
             "moe_tokens.prefill": 0}
    segs = [(0.5, steps, np.full(rows, 100))]
    pk = work.peaks("TPU v5 lite")
    row_bytes = 2 * (4096 * 2 + 1536 * 4) + 1536 * 2 + 4096 * 4
    least = (layers * steps * 3 * 4096 * 1536 * 2
             + layers * rows * steps * row_bytes) / pk["hbm_bytes_s"]
    read = lk.reader("moe_gmm_roofline")
    assert read(_rec(lk, fam, cfg, sched, least, segs)) == \
        pytest.approx(100.0)
    assert read(_rec(lk, fam, cfg, sched, 2 * least, segs)) == \
        pytest.approx(50.0)


def test_gmm_roofline_scales_the_counters_to_the_traced_calls(lk, fam, cfg):
    """The run counted four times the traced segment's tokens: a quarter
    of its rows and loads fall in the trace; prefills count apart."""
    sched = {"moe_tokens.decode": 4 * 6 * 320,
             "moe_rows_held.decode": 4 * 6 * 80,
             "moe_expert_loads.decode": 4 * 6 * 8 * 30,
             "moe_tokens.prefill": 6 * 1000,
             "moe_rows_held.prefill": 6 * 2000,
             "moe_expert_loads.prefill": 6 * 2 * 32}
    segs = [(0.5, 8, np.full(40, 100))]
    pk = work.peaks("TPU v5 lite")
    f, b = fam.gmm_work(cfg, 6 * 80, 6 * 8 * 30)
    fp, bp = fam.gmm_work(cfg, 6 * 1000, 6 * 32)
    least = (work.roofline_seconds(f, b, pk)[0]
             + work.roofline_seconds(fp, bp, pk)[0])
    got = lk.reader("moe_gmm_roofline")(
        _rec(lk, fam, cfg, sched, 4 * least, segs, [(0.6, 500, 0)]))
    assert got == pytest.approx(25.0)


def test_segment_share_counts_the_kernel_inside_the_decode_programs(lk, fam,
                                                                    cfg):
    """Kernel calls at 1-2 s and 5-6 s; the decode program runs 1-4 s
    only: 1 s of its 3 s."""
    rec = _rec(lk, fam, cfg, {}, 2.0, [], seg_runs=((1.0, 4.0),))
    assert lk.reader("moe_segment_share")(rec) == pytest.approx(100 / 3)


def test_rows_per_expert_load(lk, fam, cfg):
    read = lk.reader("moe_rows_per_expert_load")
    rec = _rec(lk, fam, cfg, {"moe_rows_held.decode": 600,
                              "moe_expert_loads.decode": 240}, 1.0, [])
    assert read(rec) == pytest.approx(2.5)
    assert read(_rec(lk, fam, cfg, {}, 1.0, [])) is None


def test_metrics_are_the_new_cells(lk):
    for name in METRICS:
        m = [m for m in lk.bench["per_layer"] if m["name"] == name][0]
        assert m["workloads"] == [CELL]
    w = lk.workload(CELL)
    assert w["chips"] == 1 and w["traffic"] == "chat"


# ------------------------------------------- the family at a CPU size
def _bench(tmp_path):
    """The test data's BENCHMARK.json with the tiny MoE cell and the
    three readers added."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = [m for m in json.load(f)["per_layer"] if m["name"] in METRICS]
    bench["configs"].append({"name": "tinymoe", "source": "test",
                             "file": "x", "why": "test",
                             "reduced": ["num_hidden_layers", "num_experts"]})
    bench["workloads"].append({"name": "tinymoe.chat", "config": "tinymoe",
                               "traffic": "tinychat", "chips": 1,
                               "why": "test"})
    bench["per_layer"] += [dict(m, workloads=["tinymoe.chat"]) for m in new]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_tiny_cell_runs_traced_and_correct(capsys, tmp_path, monkeypatch):
    monkeypatch.setitem(work.PEAKS, "cpu", {"bf16_flops": 1e12,
                                            "hbm_bytes_s": 1e11,
                                            "hbm_bytes": 1e10})
    rc = run.main(["--workload", "tinymoe.chat", "--seed", str(SEED),
                   "--seconds", "3", "--trace", "1"], roots=[DATA, CB],
                  benchmark=_bench(tmp_path), require_tpu=False,
                  out_dir=str(tmp_path / "trace"))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    # the CPU runs the grouped matmul's jnp twin: no kernel in the trace
    assert res["metrics"]["moe_rows_per_expert_load"]["value"] >= 1.0
    assert "moe_gmm_roofline" not in res["metrics"]


def test_tiny_cell_fp8_control_fails_the_check(tmp_path):
    lk = Lookup([DATA, CB], _bench(tmp_path))
    limit = lk.cell("tinymoe.chat")["check"]["widest_logit_gap"]
    got = control.readings(lk, "tinymoe.chat", [1, SEED], 3.0,
                           require_tpu=False)
    for _seed, prog, ctrl, prog_correct, ctrl_correct in got:
        assert prog <= limit < ctrl
        assert prog_correct is True and ctrl_correct is False


def test_the_family_refuses_a_width_that_differs(lk, fam):
    cfg = dict(lk.config("qwen3-moe-235b-a22b"), moe_intermediate_size=1024)
    with pytest.raises(ValueError):
        fam.lm_config(cfg)
    cfg = dict(lk.config("qwen3-moe-235b-a22b"), expert_offset=100)
    with pytest.raises(ValueError):
        fam.lm_config(cfg)
