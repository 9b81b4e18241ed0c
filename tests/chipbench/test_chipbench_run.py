"""The benchmark run end to end on the CPU at a test size (``data/``: a
Qwen2-shaped decoder 128 wide, two layers, 8,192 tokens of vocabulary),
with the look for a TPU skipped: the data-driven lookup, the output
check against the plain reference, its fp8 control, the faults the check
must catch, and the refusal to run without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import control, run, sweep  # noqa: E402
from chipbench.lookup import HERE as CB, Lookup  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
SEED = 2**33 + 17


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def cell_run(capsys, workload, roots=(DATA, CB), bench=BENCH, fault=None,
             trace=0, out_dir=None, seconds="3"):
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", seconds, "--trace", str(trace)],
                  roots=list(roots), benchmark=bench, require_tpu=False,
                  fault=fault, out_dir=out_dir)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_sound_run_is_correct(capsys):
    res = cell_run(capsys, "tiny.chat")
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                   "tpot_p95_ms", "output_tokens_per_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["tpot_p95_ms"]["unit"] == "ms"
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    gap = res["check"]["widest_logit_gap"]
    assert gap["value"] <= gap["limit"]


def _alter_tokens(eng):
    """A token altered where it is produced: the decode segment hands the
    host each row's sampled token plus one."""
    segment = eng.decode_segment
    vocab = eng.lm.cfg.vocab

    def broken(steps):
        fn = segment(steps)

        def call(*args):
            toks, logits, state, rng = fn(*args)
            return (toks + 1) % vocab, logits, state, rng
        return call
    eng.decode_segment = broken


def _stale_cache(eng):
    """Decoding that drops the newest key: every segment's rows attend
    as if their context were one token shorter."""
    segment = eng.decode_segment

    def broken(steps):
        fn = segment(steps)

        def call(params, state, logits, rng):
            c = state["caches"]
            state = dict(state, caches=c._replace(
                length=(c.length - 1).clip(0)))
            return fn(params, state, logits, rng)
        return call
    eng.decode_segment = broken


def _state_unchanged(eng):
    """A decode step that hands back its cache as it found it: the rows'
    lengths do not advance, so each next token is written over the last
    and attends the same context."""
    segment = eng.decode_segment

    def broken(steps):
        fn = segment(steps)

        def call(params, state, logits, rng):
            before = state["caches"].length.copy()
            toks, logits, state, rng = fn(params, state, logits, rng)
            state = dict(state, caches=state["caches"]._replace(
                length=before))
            return toks, logits, state, rng
        return call
    eng.decode_segment = broken


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _stale_cache],
                         ids=["token_altered", "state_unchanged",
                              "context_short"])
def test_faults_are_caught(capsys, fault):
    res = cell_run(capsys, "tiny.chat", fault=fault)
    assert res["correct"] is False
    gap = res["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_prefix_path_is_checked(capsys):
    res = cell_run(capsys, "tiny.doc")
    assert res["correct"] is True


def test_traced_run_reports_per_layer_metrics(capsys, tmp_path, monkeypatch):
    from chipbench import work
    monkeypatch.setitem(work.PEAKS, "cpu", {"bf16_flops": 1e12,
                                            "hbm_bytes_s": 1e11,
                                            "hbm_bytes": 1e10})
    res = cell_run(capsys, "tiny.doc", trace=1, out_dir=str(tmp_path))
    assert res["correct"] is True
    assert {"queue_wait_p95_ms", "prefix_hit_token_share",
            "compiles_in_window"} <= set(res["metrics"])
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert "ttft_p95_ms" not in res["metrics"]


def test_a_new_config_mix_and_metric_need_no_edit(capsys, tmp_path):
    """A later change adds a configuration, a mix, a cell and a metric by
    adding files under a root of its own and entries in BENCHMARK.json."""
    for kind in ("configs", "traffic", "cells", "metrics"):
        (tmp_path / kind).mkdir()
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-deep", num_hidden_layers=3)
    (tmp_path / "configs" / "tiny-deep.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({
        "menu": {"step": 16, "max": 64},
        "prompt": {"choice": [32, 48]},
        "output": {"uniform_int": [4, 8], "min": 4, "max": 8}}))
    (tmp_path / "cells" / "tiny-deep.burst.json").write_text(json.dumps(
        {"rate": 3.0, "check": {"widest_logit_gap": 0.05}}))
    (tmp_path / "metrics" / "requests_done.py").write_text(
        "def read(rec):\n"
        "    return sum(r.done for r in rec.recs.values())\n")
    with open(BENCH) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-deep", "source": "test",
                             "file": "x", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-deep.burst",
                               "config": "tiny-deep", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "requests_done", "unit": "requests",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny-deep.burst"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    lk = Lookup([str(tmp_path), DATA, CB], str(path))
    assert lk.config("tiny-deep")["num_hidden_layers"] == 3
    assert [m["name"] for m in lk.metrics("tiny.chat", "end_to_end")] == \
        ["ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "output_tokens_per_s",
         "setup_s"]
    res = cell_run(capsys, "tiny-deep.burst", roots=(tmp_path, DATA, CB),
                   bench=str(path))
    assert res["correct"] is True
    assert res["metrics"]["requests_done"]["value"] == res["attempted"] == 9


def test_fp8_control_fails_the_check():
    """The control at the test size: the reference computed in fp8 puts
    first tokens that the full-precision reference ranks far below its
    best, past the cell's limit, on every seed; the program's own served
    tokens stay inside it."""
    lk = Lookup([DATA, CB], BENCH)
    limit = lk.cell("tiny.chat")["check"]["widest_logit_gap"]
    got = control.readings(lk, "tiny.chat", [1, 2, SEED], 3.0,
                           require_tpu=False)
    for _seed, prog, ctrl, prog_correct, ctrl_correct in got:
        assert prog <= limit < ctrl
        assert prog_correct is True and ctrl_correct is False


def test_sweep_reports_each_rate_and_a_knee():
    """One set-up, several rates back to back, each after the cell's
    lead-in: the requests due against those admitted, the queue at the
    window's opening and close, and the end-to-end metrics."""
    lk = Lookup([DATA, CB], BENCH)
    rows, knee = sweep.sweep(lk, "tiny.chat", [2.0, 4.0], 3.0, SEED, 5.0,
                             require_tpu=False)
    assert [r["rate"] for r in rows] == [2.0, 4.0]
    assert [r["requests_due"] for r in rows] == [6, 12]
    for r in rows:
        assert r["lead_in_s"] == 1.0 and r["failed"] == 0
        assert r["queue_at_close"] == r["requests_due"] - \
            r["admitted_in_window"]
        assert all(r[m] > 0 for m in sweep.E2E)
    assert knee == 4.0


def test_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "tiny.chat", "--seed", "1", "--seconds",
                   "1"], roots=[DATA, CB], benchmark=BENCH)
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_benchmark_alone_prints_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to serve: the run fails and prints nothing."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
