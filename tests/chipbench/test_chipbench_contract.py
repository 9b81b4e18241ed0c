"""BENCHMARK.json as the benchmark reads it: every name resolves to a file
of its own, every configuration file names a family whose code finds the
program's configuration at its published widths, every cell's chips are
its configuration's mesh, and every name and unit keeps to its
alphabet."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import run, traffic  # noqa: E402
from chipbench.lookup import Lookup  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]
#: every configuration file, those no cell runs yet included
CONFIGS = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "chipbench", "configs")) if f.endswith(".json"))


@pytest.fixture(scope="module")
def lk():
    return Lookup()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_every_metric_has_a_reader(lk, m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(lk.reader(m["name"]))
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=CELLS)
def test_every_cell_resolves(lk, w):
    assert NAME.match(w["name"]) and len(w["why"]) <= 200
    assert w["chips"] in (1, 4)
    cfg, mix, cell = lk.config(w["config"]), lk.mix(w["traffic"]), \
        lk.cell(w["name"])
    assert cell["rate"] > 0 and cell["check"]["widest_logit_gap"] > 0
    assert cell.get("lead_in_s", 0.0) >= 0
    assert traffic.max_context(mix) + 8 <= cfg["serve"]["max_seq"]
    assert int(mix["output"]["max"]) <= cfg["serve"]["max_seq"]
    assert run.mesh_size(cfg) == w["chips"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_are_the_programs_configs(lk, name):
    cfg = lk.config(name)
    assert cfg["name"] == name
    for c in BENCH["configs"]:
        if c["name"] == name:
            assert os.path.join(ROOT, c["file"]) == os.path.join(
                ROOT, "chipbench", "configs", name + ".json")
            assert sorted(c["reduced"]) == sorted(cfg["reduced"])
            assert cfg["source"] == c["source"]
    assert {c["name"] for c in BENCH["configs"]} <= set(CONFIGS)
    # the family's code raises on any width that differs
    lc = lk.family(cfg["family"]).lm_config(cfg)
    assert lc.n_layers == cfg["num_hidden_layers"]
    for key, published in cfg["reduced"].items():
        assert cfg[key] < published       # a cut, never a widening


def test_a_width_that_differs_is_refused(lk):
    cfg = dict(lk.config("qwen2-0.5b"), intermediate_size=4096)
    with pytest.raises(ValueError):
        lk.family("qwen2").lm_config(cfg)


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
