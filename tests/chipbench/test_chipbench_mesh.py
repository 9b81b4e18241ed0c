"""The benchmark's seam to the model and its mesh path, on the CPU: a
family found by name (``families/<family>.py`` under any search root),
and a cell on a (1, 4) (data, model) mesh of four host devices
(``tiny.tp4.chat``: one KV head on each), run end to end against the plain
reference on the same sharded weights, with a fault the check must
catch.  The mesh runs in a subprocess: this process's JAX holds one CPU
device."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import run  # noqa: E402
from chipbench.lookup import HERE as CB, Lookup  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
SEED = 2**33 + 17

#: two runs of the mesh cell in one process (they share its compiles): a
#: sound one, and one whose decode segments find the KV-head slices of
#: devices 0 and 1 swapped
MESH_RUNS = r"""
import json, os, sys
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax
from chipbench import run

PERM = jax.numpy.array([1, 0, 2, 3])


def swap_heads(eng):
    segment = eng.decode_segment

    def broken(steps):
        fn = segment(steps)

        def call(params, state, logits, rng):
            c = state["caches"]
            swap = lambda a: jax.device_put(a[..., PERM, :], a.sharding)
            state = dict(state, caches=c._replace(k_pages=swap(c.k_pages),
                                                  v_pages=swap(c.v_pages)))
            return fn(params, state, logits, rng)
        return call
    eng.decode_segment = broken


for fault in (None, swap_heads):
    run.main(["--workload", "tiny.tp4.chat", "--seed", sys.argv[4],
              "--seconds", "3", "--trace", "0"], roots=[sys.argv[2],
              os.path.join(sys.argv[1], "chipbench")],
             benchmark=sys.argv[3], require_tpu=False, fault=fault)
"""


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jax")))
    p = subprocess.run([sys.executable, "-c", MESH_RUNS, ROOT, DATA, BENCH,
                        str(SEED)], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2, p.stdout[-2000:]
    return lines


def test_mesh_cell_is_correct_on_four_devices(mesh_runs):
    res = mesh_runs[0]
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    assert res["device"]["count"] == 4
    gap = res["check"]["widest_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_swapped_kv_heads_across_devices_are_caught(mesh_runs):
    res = mesh_runs[1]
    assert res["correct"] is False
    gap = res["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_family_seam_resolves_by_name():
    lk = Lookup([DATA, CB], BENCH)
    fam = lk.family(lk.config("tiny")["family"])
    assert fam.__file__ == os.path.join(CB, "families", "qwen2.py")
    for name in ("make_weights", "engine", "widest_gap",
                 "layer_matmul_params", "kv_bytes_per_token"):
        assert callable(getattr(fam, name))
    assert lk.family("qwen2") is fam          # loaded once
    with pytest.raises(FileNotFoundError, match="families/no-such"):
        lk.family("no-such")


def test_a_second_family_is_found_in_the_test_data(capsys, tmp_path):
    """A configuration names a family that lives only under the test
    data's root; the run finds it by that name and reaches the model
    through it alone."""
    (tmp_path / "configs").mkdir()
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-echo", family="qwen2_echo")
    (tmp_path / "configs" / "tiny-echo.json").write_text(json.dumps(cfg))
    (tmp_path / "cells").mkdir()
    (tmp_path / "cells" / "tiny-echo.chat.json").write_text(json.dumps(
        {"rate": 4.0, "check": {"widest_logit_gap": 0.05}}))
    with open(BENCH) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-echo.chat",
                               "config": "tiny-echo", "traffic": "tinychat",
                               "chips": 1, "why": "test"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    lk = Lookup([str(tmp_path), DATA, CB], str(path))
    assert lk.family("qwen2_echo").__file__ == os.path.join(
        DATA, "families", "qwen2_echo.py")
    rc = run.main(["--workload", "tiny-echo.chat", "--seed", str(SEED),
                   "--seconds", "2"], roots=[str(tmp_path), DATA, CB],
                  benchmark=str(path), require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert "[family] qwen2_echo makes the weights of tiny-echo" in err


def test_a_cell_whose_chips_are_not_its_mesh_is_refused(tmp_path):
    with open(BENCH) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] == "tiny.tp4.chat":
            w["chips"] = 1
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="mesh has 4"):
        run.system(Lookup([DATA, CB], str(path)), "tiny.tp4.chat", False)
