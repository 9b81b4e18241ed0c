"""Mesh-aware sharded serving: registry mesh facts / per-sharding tune
keys, Engine mesh validation + (1,1)-mesh parity in-suite, and the full
8-simulated-device bench (parity across shapes, per-sharding warm start,
kill-a-device degradation) as a slow subprocess test — the in-suite jax
runtime is pinned to 1 real CPU device by design (see conftest)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from repro.kernels import registry
from repro.launch.mesh import ServeMesh, axis_ici_map, make_serve_mesh
from repro.serve import BatchScheduler, Engine, Request, ServeConfig

SCFG = dict(max_seq=128, batch_slots=2, temperature=0.0, admission_chunk=8)


# ---------------------------------------------------------------------------
# registry: mesh facts and per-sharding tune keys
# ---------------------------------------------------------------------------

def test_mesh_key_tag_and_unsharded_identity():
    assert registry.mesh_key_tag() == ""
    assert registry.mesh_key_tag(mesh_shape=None, per_device_heads=3) == ""
    tag = registry.mesh_key_tag(mesh_shape=(1, 2), mesh_axis="model",
                                per_device_heads=2)
    assert tag == "-mesh1x2.model.pdh2"
    # the unsharded key is byte-identical to the pre-mesh spelling
    import jax.numpy as jnp
    base = dict(b=1, h=4, kvh=2, sq=8, sk=8, dh=8, dtype=jnp.float32)
    assert registry.attention_tune_key(**base) == \
        registry.attention_tune_key(**base, mesh_shape=None)
    sharded = registry.attention_tune_key(**base, mesh_shape=(1, 2),
                                          mesh_axis="model",
                                          per_device_heads=1)
    assert sharded == registry.attention_tune_key(**base) \
        + "-mesh1x2.model.pdh1"


def test_use_mesh_facts_scoping_and_validation():
    assert registry.mesh_facts() == {}
    with registry.use_mesh_facts(mesh_shape=(1, 2), per_device_heads=2):
        assert registry.mesh_facts() == {"mesh_shape": (1, 2),
                                         "per_device_heads": 2}
        with registry.use_mesh_facts(per_device_heads=1):   # inner wins
            assert registry.mesh_facts()["per_device_heads"] == 1
            assert registry.mesh_facts()["mesh_shape"] == (1, 2)
        assert registry.mesh_facts()["per_device_heads"] == 2
    assert registry.mesh_facts() == {}
    with pytest.raises(ValueError, match="unknown mesh facts"):
        with registry.use_mesh_facts(mesh_rank=2):
            pass
    with registry.use_mesh_facts(mesh_shape=None):          # None dropped
        assert registry.mesh_facts() == {}


def test_best_falls_back_to_unsharded_neighbor():
    import jax.numpy as jnp
    facts = dict(b=1, h=4, kvh=2, sq=64, sk=64, dh=8, dtype=jnp.float32,
                 backend="cpu")
    key = registry.attention_tune_key(**facts)
    registry.record("attention", key, (64, 64))
    # no record exists for THIS sharding; the unsharded bucket is the
    # fallback neighbor of last resort
    with registry.use_mesh_facts(mesh_shape=(1, 2), mesh_axis="model",
                                 per_device_heads=1):
        assert registry.best("attention", **facts) == (64, 64)


def test_supports_rejects_indivisible_head_sharding():
    for family, impl in (("attention", "pallas_flash"),
                         ("paged_decode", "pallas_paged")):
        sup = registry.get_spec(family, impl).supports
        assert sup(per_device_heads=1)
        assert not sup(per_device_heads=0)    # 0 marks indivisible kvh
        assert sup(per_device_heads=None)     # unsharded: unaffected
    q8 = registry.get_spec("paged_decode", "pallas_paged_q8").supports
    assert q8(quantized=True, per_device_heads=2)
    assert not q8(quantized=True, per_device_heads=0)


# ---------------------------------------------------------------------------
# ServeMesh + Engine validation (1 real device in-suite)
# ---------------------------------------------------------------------------

def test_make_serve_mesh_single_device():
    sm = make_serve_mesh((1, 1))
    assert isinstance(sm, ServeMesh)
    assert sm.axis_names == ("data", "model")
    assert sm.device_ids == (0,)
    assert sm.spares == ()
    assert [r["axis"] for r in axis_ici_map(sm.topo, sm.device_ids,
                                            (1, 1), sm.axis_names)] \
        == ["data", "model"]


def test_make_serve_mesh_too_big_raises():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="mesh needs"):
        make_serve_mesh((1, n + 1))


def test_engine_rejects_mesh_without_model_axis(tiny_lm, tiny_params):
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="'model' axis"):
        Engine(tiny_lm, tiny_params, ServeConfig(**SCFG), mesh=mesh)


def test_engine_rejects_indivisible_kv_heads(tiny_lm, tiny_params):
    class FakeMesh:                     # validation fires before any use
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 3}
    with pytest.raises(ValueError, match="num_kv_heads"):
        Engine(tiny_lm, tiny_params, ServeConfig(**SCFG), mesh=FakeMesh())


def test_state_spec_shards_pool_lanes_not_recurrent_states():
    """Under a model axis of 2 a paged engine's lane-dense pools
    [L,P,ps,KVH*Dh] split their last dim (whole heads a device), and its
    int8 scale pools, page tables and lengths replicate; in an engine
    without pages a rank-4 recurrent state replicates and a dense cache
    [L,B,S,KVH,Dh] splits its dim -2."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.models.attention import PagedKVCache
    mesh = SimpleNamespace(shape={"model": 2})
    spec = lambda paged, x: Engine._state_spec(
        SimpleNamespace(mesh=mesh, paged=paged), x)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    pool = PagedKVCache(k_pages=sds(2, 9, 16, 128), v_pages=sds(2, 9, 16, 128),
                        page_table=sds(2, 4, 8), length=sds(2, 4),
                        k_scale=sds(2, 9, 16), v_scale=sds(2, 9, 16))
    got = jax.tree.map(lambda x: spec(True, x), pool)
    lanes = P(None, None, None, "model")
    assert (got.k_pages, got.v_pages) == (lanes, lanes)
    assert got.page_table == got.length == got.k_scale == got.v_scale == P()
    assert spec(False, sds(2, 4, 8, 16)) == P()
    assert spec(False, sds(2, 4, 32, 2, 64)) == P(None, None, None, "model",
                                                  None)


def test_trivial_mesh_engine_matches_unsharded(tiny_lm, tiny_params):
    prompts = [[1, 2, 3, 4], [7, 5, 3]]
    ref = Engine(tiny_lm, tiny_params, ServeConfig(**SCFG)).generate(
        prompts, max_new_tokens=8)
    sm = make_serve_mesh((1, 1))
    eng = Engine(tiny_lm, tiny_params, ServeConfig(**SCFG), mesh=sm)
    assert eng.mesh_facts == {"mesh_shape": (1, 1), "mesh_axis": "model",
                              "per_device_heads":
                                  tiny_lm.cfg.num_kv_heads}
    assert eng.generate(prompts, max_new_tokens=8) == ref
    # the shared LM is not mutated: a later unsharded engine still works
    assert tiny_lm.mesh is None


def test_scheduler_ft_armed_only_on_serve_mesh(tiny_lm, tiny_params):
    eng = Engine(tiny_lm, tiny_params, ServeConfig(**SCFG))
    sched = BatchScheduler(eng)
    assert sched.heartbeats is None
    with pytest.raises(RuntimeError, match="ServeMesh"):
        sched.inject_failure(0)
    sm = make_serve_mesh((1, 1))
    meng = Engine(tiny_lm, tiny_params, ServeConfig(**SCFG), mesh=sm)
    msched = BatchScheduler(meng)
    assert msched.heartbeats is not None
    for rid in range(3):
        msched.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                              max_new_tokens=12))
    done = msched.run()
    # healthy run: ft ticked every segment, nothing confirmed, no event
    assert len(done) == 3
    assert msched.metrics["remeshes"] == 0
    assert msched.ft_events == []


# ---------------------------------------------------------------------------
# the full multi-device story (8 simulated devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mesh_bench_end_to_end(tmp_path):
    """bench_mesh under 8 simulated devices, twice: token parity across
    (1,2) and (1,4), a killed device degrading onto the hot spare with
    parity intact, and the second (fresh) process warm-starting every
    per-sharding tune record with 0 sweeps / 0 lowerings."""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep + root
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_CACHE_DIR"] = str(tmp_path / "cache")

    def run(tag):
        out = tmp_path / f"BENCH_mesh.{tag}.json"
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_mesh", "--smoke",
             "--json", str(out)],
            capture_output=True, text=True, env=env, cwd=root, timeout=540)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        with open(out) as fh:
            return json.load(fh)

    first = run("cold")
    assert first["devices"] == 8
    assert first["parity"] is True
    assert [s["shape"] for s in first["shapes"]] == [[1, 2], [1, 4]]
    deg = first["degradation"]
    assert deg["remeshes"] >= 1 and deg["token_parity_after"] is True
    ev = [e for e in deg["events"] if e["type"] == "remesh"][0]
    assert ev["remesh_latency_s"] > 0
    assert deg["killed"] not in ev["device_ids"]

    second = run("warm")
    assert second["parity"] is True
    assert second["tune"], "per-sharding tune records missing"
    for rec in second["tune"]:
        assert rec["swept"] is False and rec["lowerings"] == 0, rec
    # distinct shardings persisted under distinct keys
    keys = {rec["key"] for rec in second["tune"]}
    assert len(keys) == len(second["tune"])
