"""The kernel registry (kernels/registry.py): declarative impls, ONE
override ladder for every family, and disk-persistent autotuning.

The PR's acceptance surface: one ``select/run/autotune/best`` entry point
serves attention, paged decode, and the three newly-onboarded families;
the override-precedence matrix (context > ``REPRO_IMPL`` > legacy
``REPRO_ATTN_IMPL`` > heuristics, plus ``ServeConfig.impls``) holds for
every registered family including the legacy shim names and the
``paged_decode`` decode-side-pin semantics; the tune table is
lock-guarded under concurrent sweeps; the flash tune key buckets batch
to powers of two; and a fresh process warm-starts from the persisted
tune table with zero sweeps and zero lowerings.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifact_cache import ArtifactCache
from repro.core.session import ProfileSession
from repro.kernels import autotune, dispatch, ref, registry

FAMILIES = ("attention", "paged_decode", "stream_triad", "jacobi7",
            "ssd_scan")

#: static facts that drive each family's heuristic on a jnp host
HEUR_FACTS = {
    "attention": dict(sq=256, sk=256, dh=64, backend="cpu"),
    "paged_decode": dict(backend="cpu"),
    "stream_triad": dict(backend="cpu"),
    "jacobi7": {},
    "ssd_scan": dict(backend="cpu"),
}
#: ... and what they pick there / what an override flips them to
HEUR_WANT = {"attention": "full", "paged_decode": "jnp_paged",
             "stream_triad": "xla_triad", "jacobi7": "wavefront",
             "ssd_scan": "jnp_scan"}
OTHER = {"attention": "pallas_flash", "paged_decode": "pallas_paged",
         "stream_triad": "pallas_triad", "jacobi7": "naive",
         "ssd_scan": "pallas_ssd"}


# ---------------------------------------------------------------------------
# the registry is declarative and complete
# ---------------------------------------------------------------------------

def test_registry_declares_every_family():
    assert set(FAMILIES) <= set(registry.families())
    for fam in FAMILIES:
        names = registry.impls(fam)
        assert len(names) >= 2, fam
        specs = [registry.get_spec(fam, n) for n in names]
        # every family has at least one tunable impl with a full tune
        # space; paged_decode carries two (fp + q8, disjoint key
        # prefixes so their tune records never collide)
        tuned = [s for s in specs if s.tune is not None]
        assert len(tuned) == (2 if fam == "paged_decode" else 1), fam
        for spec in tuned:
            ts = spec.tune
            assert callable(ts.key) and callable(ts.candidates)
            assert callable(ts.vmem) and callable(ts.probe)
        for s in specs:
            assert s.oracle.startswith("repro.kernels.ref."), (fam, s.name)
            assert s.layout, (fam, s.name)
    assert "tunable" in registry.describe()


def test_unknown_family_and_impl_raise():
    with pytest.raises(ValueError, match="unknown kernel family"):
        registry.select("bogus")
    with pytest.raises(ValueError, match="unknown attention impl"):
        registry.get_spec("attention", "bogus")
    with pytest.raises(ValueError):
        registry.run("attention", None, None, None, impl="bogus")


def test_parse_impl_spec():
    got = registry.parse_impl_spec(
        "attention=pallas_flash, paged_decode=pallas_paged")
    assert got == {"attention": "pallas_flash",
                   "paged_decode": "pallas_paged"}
    assert registry.parse_impl_spec("") == {}
    for bad in ("attention", "nope=full", "attention=nope"):
        with pytest.raises(ValueError):
            registry.parse_impl_spec(bad)


# ---------------------------------------------------------------------------
# the override-precedence matrix, per family (the satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_override_precedence_matrix(family, monkeypatch):
    facts = HEUR_FACTS[family]
    # 1. unforced: the heuristic
    assert registry.select(family, **facts) == HEUR_WANT[family]
    # 2. REPRO_IMPL env beats heuristics
    monkeypatch.setenv("REPRO_IMPL", f"{family}={OTHER[family]}")
    assert registry.select(family, **facts) == OTHER[family]
    # 3. use_impl context beats env
    with registry.use_impl(**{family: HEUR_WANT[family]}):
        assert registry.select(family, **facts) == HEUR_WANT[family]
        # 4. inner context beats outer (and restores)
        with registry.use_impl(**{family: OTHER[family]}):
            assert registry.select(family, **facts) == OTHER[family]
        assert registry.select(family, **facts) == HEUR_WANT[family]
    assert registry.select(family, **facts) == OTHER[family]   # env again
    # 5. an env that names only OTHER families falls through to heuristics
    other_fam = "jacobi7" if family != "jacobi7" else "attention"
    monkeypatch.setenv("REPRO_IMPL",
                       f"{other_fam}={OTHER[other_fam]}")
    assert registry.select(family, **facts) == HEUR_WANT[family]
    # 6. None values are no-ops in the context
    with registry.use_impl(**{family: None}):
        assert registry.override_for(family) is None


def test_env_repro_impl_validates_at_selection(monkeypatch):
    for bad in ("attention=bogus", "bogusfam=full", "attention"):
        monkeypatch.setenv("REPRO_IMPL", bad)
        with pytest.raises(ValueError):
            registry.select("attention", sq=8, sk=8, dh=8)


def test_use_impl_spec_string_form():
    with registry.use_impl("attention=jnp_flash,ssd_scan=pallas_ssd"):
        assert registry.override_for("attention") == "jnp_flash"
        assert registry.override_for("ssd_scan") == "pallas_ssd"
        assert registry.override_for("jacobi7") is None


# ---------------------------------------------------------------------------
# legacy shims: REPRO_ATTN_IMPL / use_attention_impl map onto both families
# ---------------------------------------------------------------------------

def test_legacy_context_mapping_per_name():
    for name, mapping in registry.LEGACY_ATTN_MAP.items():
        with dispatch.use_attention_impl(name):
            for fam in ("attention", "paged_decode"):
                assert registry.override_for(fam) == mapping.get(fam), \
                    (name, fam)
    assert registry.override_for("attention") is None          # restored


def test_legacy_paged_decode_pin_is_decode_side_only():
    with dispatch.use_attention_impl("paged_decode"):
        # decode side pinned to the Pallas kernel ...
        assert registry.select("paged_decode", backend="cpu") \
            == "pallas_paged"
        # ... transparent to prefill (heuristics, not an error)
        assert registry.select("attention", sq=256, sk=256, dh=64,
                               backend="cpu") == "full"
        assert dispatch.attention_impl_override() == "paged_decode"


def test_legacy_env_loses_to_repro_impl(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_IMPL", "full")
    assert registry.select("attention", **HEUR_FACTS["attention"]) == "full"
    # the legacy name maps the decode side too (full -> gather reference)
    assert registry.select("paged_decode", backend="tpu") == "jnp_paged"
    monkeypatch.setenv("REPRO_IMPL", "attention=jnp_flash")
    assert registry.select("attention", **HEUR_FACTS["attention"]) \
        == "jnp_flash"
    # families REPRO_IMPL does not name still take the legacy mapping
    assert registry.select("paged_decode", backend="tpu") == "jnp_paged"
    # legacy names never touch the new families
    assert registry.select("stream_triad", backend="tpu") == "pallas_triad"


def test_legacy_env_validates(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_IMPL", "bogus")
    with pytest.raises(ValueError, match="REPRO_ATTN_IMPL"):
        registry.select("attention", sq=8, sk=8, dh=8)


# ---------------------------------------------------------------------------
# ServeConfig: the engine pins through the same ladder
# ---------------------------------------------------------------------------

def test_serveconfig_impls_pin(tiny_lm):
    from repro.serve.engine import Engine, ServeConfig
    eng = Engine(tiny_lm, None, ServeConfig(
        max_seq=64, impls={"attention": "pallas_flash",
                           "ssd_scan": "pallas_ssd"}))
    with eng._impl_ctx():
        assert registry.select("attention", **HEUR_FACTS["attention"]) \
            == "pallas_flash"
        assert registry.select("ssd_scan", backend="cpu") == "pallas_ssd"
    assert registry.select("attention", **HEUR_FACTS["attention"]) == "full"


def test_serveconfig_impls_beat_legacy_attn_impl_per_family(tiny_lm):
    from repro.serve.engine import Engine, ServeConfig
    eng = Engine(tiny_lm, None, ServeConfig(
        max_seq=64, attn_impl="full", impls={"attention": "jnp_flash"}))
    with eng._impl_ctx():
        # impls wins for the family it names ...
        assert registry.select("attention", **HEUR_FACTS["attention"]) \
            == "jnp_flash"
        # ... while the legacy name keeps pinning the decode side
        assert registry.select("paged_decode", backend="tpu") == "jnp_paged"


def test_serveconfig_impls_validation(tiny_lm):
    from repro.serve.engine import Engine, ServeConfig
    with pytest.raises(ValueError, match="unknown attention impl"):
        Engine(tiny_lm, None,
               ServeConfig(max_seq=64, impls={"attention": "bogus"}))
    with pytest.raises(ValueError, match="page_size"):
        Engine(tiny_lm, None,
               ServeConfig(max_seq=64,
                           impls={"paged_decode": "pallas_paged"}))


# ---------------------------------------------------------------------------
# the onboarded families run through the registry and match their oracles
# ---------------------------------------------------------------------------

def test_stream_triad_impls_match_oracle():
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    b = jax.random.normal(ks[0], (128 * 4,), jnp.float32)
    c = jax.random.normal(ks[1], (128 * 4,), jnp.float32)
    want = ref.stream_triad(None, b, c, 2.5)
    for impl in registry.impls("stream_triad"):
        got = registry.run("stream_triad", b, c, impl=impl, s=2.5,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    # run() with no impl self-selects (xla_triad on a jnp host)
    got = registry.run("stream_triad", b, c, s=2.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_jacobi7_impls_match_oracle():
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 10, 10), jnp.float32)
    want = ref.jacobi7_valid(x, sweeps=2)
    for impl in registry.impls("jacobi7"):
        got = registry.run("jacobi7", x, impl=impl, sweeps=2,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_ssd_scan_impls_match_oracle():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, h, dk, dv = 1, 32, 2, 8, 8
    q = jax.random.normal(ks[0], (b, s, h, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, s, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, s, h, dv)) * 0.3
    lf = -jnp.abs(jax.random.normal(ks[3], (b, s, h))) * 0.1
    li = -jnp.abs(jax.random.normal(ks[4], (b, s, h))) * 0.1
    want_y, (want_c, want_n) = ref.ssd_scan(q, k, v, lf, li)
    for impl in registry.impls("ssd_scan"):
        y, (c_st, n_st) = registry.run("ssd_scan", q, k, v, lf, li,
                                       impl=impl, chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(c_st), np.asarray(want_c),
                                   rtol=1e-4, atol=1e-5)


def test_run_attention_self_selects_by_facts():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 16, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 16, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 16, 2, 16), jnp.float32)
    want = ref.flash_attention(q, k, v, causal=True)
    got = registry.run("attention", q, k, v, causal=True)   # impl=None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# generic autotune: persisted winners, fresh-process warm start
# ---------------------------------------------------------------------------

TRIAD_N = 128 * 256
TRIAD_CANDS = ((64,), (128,))


def test_autotune_persists_and_fresh_process_warm_starts(tmp_path,
                                                         monkeypatch):
    registry.clear_tune_table()
    try:
        cache_dir = str(tmp_path / "cache")
        cold = ProfileSession(cache_dir=cache_dir)
        rec = registry.autotune("stream_triad", cold, n=TRIAD_N,
                                candidates=TRIAD_CANDS)
        assert rec.swept and rec.lowerings == len(TRIAD_CANDS)
        assert rec.choice in TRIAD_CANDS

        # warm, same process: the persisted record, no measuring
        warm = ProfileSession(cache=ArtifactCache(cache_dir))
        rec2 = registry.autotune("stream_triad", warm, n=TRIAD_N,
                                 candidates=TRIAD_CANDS)
        assert not rec2.swept and warm.lowerings == 0
        assert rec2.choice == rec.choice and rec2.scores == rec.scores

        # "fresh process": wipe the in-memory table, keep the disk —
        # autotune warm-starts with ZERO sweeps and ZERO lowerings
        registry.clear_tune_table()
        fresh = ProfileSession(cache=ArtifactCache(cache_dir))
        rec3 = registry.autotune("stream_triad", fresh, n=TRIAD_N,
                                 candidates=TRIAD_CANDS)
        assert not rec3.swept and fresh.lowerings == 0

        # best() alone (dispatch's path) resolves from the disk table,
        # no autotune call in this "process" at all
        registry.clear_tune_table()
        monkeypatch.setenv("REPRO_CACHE_DIR", cache_dir)
        assert registry.best("stream_triad", n=TRIAD_N) == rec.choice
        # an untuned shape still gets the declared default
        assert registry.best("stream_triad", n=TRIAD_N * 2) \
            == (registry.DEFAULT_BLOCK_ROWS,)
    finally:
        registry.clear_tune_table()


def test_autotune_candidate_change_resweeps(tmp_path):
    registry.clear_tune_table()
    try:
        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        rec = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                candidates=((64,),))
        assert rec.swept
        # same key, different candidate set: the persisted record does
        # not match the request, so it re-sweeps (probes still cached)
        rec2 = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                 candidates=TRIAD_CANDS)
        assert rec2.swept and set(rec2.scores) == set(TRIAD_CANDS)
        # and force=True ignores the stored record outright
        rec3 = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                 candidates=TRIAD_CANDS, force=True)
        assert rec3.swept and rec3.lowerings == 0   # probes all disk-warm
    finally:
        registry.clear_tune_table()


def test_autotune_vmem_gate_and_no_fit():
    registry.clear_tune_table()
    try:
        sess = ProfileSession(enabled=False)
        # budget sized so (64,) fits and (128,) does not
        rec = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                candidates=((64,), (128,)),
                                vmem_fraction=2e-2)
        assert rec.scores[(128,)] == float("inf")    # gated, never lowered
        assert rec.choice == (64,) and sess.lowerings == 1
        with pytest.raises(ValueError, match="fits VMEM"):
            registry.autotune("stream_triad", sess, n=TRIAD_N,
                              candidates=((128,),), vmem_fraction=1e-9)
    finally:
        registry.clear_tune_table()


def test_best_negative_caches_disk_misses_until_recorded():
    registry.clear_tune_table()
    try:
        n = 128 * 64
        key = registry.triad_tune_key(n=n, dtype=jnp.float32)
        assert registry.best("stream_triad", n=n) \
            == (registry.DEFAULT_BLOCK_ROWS,)
        # the disk miss is negative-cached (one filesystem probe per
        # process per key); recording the key supersedes the marker
        registry.record("stream_triad", key, (64,))
        assert registry.best("stream_triad", n=n) == (64,)
    finally:
        registry.clear_tune_table()


def test_best_reads_custom_tune_roots_registered_by_autotune(tmp_path):
    registry.clear_tune_table()
    try:
        sess = ProfileSession(cache_dir=str(tmp_path / "elsewhere"))
        rec = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                candidates=TRIAD_CANDS)
        # a family-scoped clear drops the records but keeps the learned
        # cache root: dispatch still finds the winner on disk even
        # though $REPRO_CACHE_DIR points somewhere else
        registry.clear_tune_table("stream_triad")
        assert registry.best("stream_triad", n=TRIAD_N) == rec.choice
        # a FULL clear forgets the root too -> declared default again
        registry.clear_tune_table()
        assert registry.best("stream_triad", n=TRIAD_N) \
            == (registry.DEFAULT_BLOCK_ROWS,)
    finally:
        registry.clear_tune_table()


def test_manual_record_and_dump():
    registry.clear_tune_table()
    try:
        n = 128 * 1024
        key = registry.triad_tune_key(n=n, dtype=jnp.float32)
        registry.record("stream_triad", key, (512,))
        assert registry.best("stream_triad", n=n) == (512,)
        dump = registry.dump_tune_table()
        assert dump["records"][0]["choice"] == [512]
        assert dump["records"][0]["family"] == "stream_triad"
        assert dump["records"][0]["swept"] is False
    finally:
        registry.clear_tune_table()


# ---------------------------------------------------------------------------
# satellite: the tune table is lock-guarded under concurrent sweeps
# ---------------------------------------------------------------------------

def test_concurrent_sweeps_do_not_race_the_table(tmp_path):
    """ProfileSession.sweep workers autotune DISTINCT shapes and the SAME
    shape concurrently; the lock-guarded table must end up with every
    record and no worker may observe a torn one (the legacy
    _TABLE/_PAGED_TABLE dicts had no lock)."""
    registry.clear_tune_table()
    try:
        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        ns = [128 * 128 * (i + 1) for i in range(4)]

        def cell_fn(arch, shape):
            rec = registry.autotune("stream_triad", sess, n=int(shape),
                                    candidates=TRIAD_CANDS)
            return {"n": int(shape), "choice": rec.choice}

        # duplicate every shape so workers also collide on one key
        shapes = [str(n) for n in ns] * 2
        recs = sess.sweep(["triad"], shapes, parallel=4, cell_fn=cell_fn)
        assert len(recs) == len(shapes)
        failed = [r for r in recs if r.get("status") == "FAILED"]
        assert not failed, failed
        # every shape resolved and recorded; lookups agree with workers
        by_n = {}
        for r in recs:
            by_n.setdefault(r["n"], set()).add(r["choice"])
        for n in ns:
            assert len(by_n[n]) == 1                # no torn records
            assert registry.best("stream_triad", n=n) in TRIAD_CANDS
        # the per-digest session lock also deduped compiles: each
        # (shape, candidate) lowered at most once
        assert sess.lowerings <= len(ns) * len(TRIAD_CANDS)
    finally:
        registry.clear_tune_table()


def test_use_impl_is_thread_local():
    seen = {}

    def worker():
        seen["worker"] = registry.override_for("attention")

    with registry.use_impl(attention="jnp_flash"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["worker"] is None      # the context never leaked threads


# ---------------------------------------------------------------------------
# satellite: flash tune_key buckets batch to powers of two
# ---------------------------------------------------------------------------

def test_flash_tune_key_buckets_batch(tmp_path):
    registry.clear_tune_table()
    try:
        shape = dict(h=4, kvh=2, sq=64, sk=64, dh=32)
        dt = dict(dtype=jnp.float32, causal=True)
        # the scheduler's live mix varies b; keys must agree per bucket
        assert autotune.tune_key(b=3, **shape, **dt) \
            == autotune.tune_key(b=4, **shape, **dt)
        assert autotune.tune_key(b=4, **shape, **dt) \
            != autotune.tune_key(b=5, **shape, **dt)

        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        rec = autotune.autotune_flash_blocks(
            b=4, **shape, session=sess, candidates=((32, 32), (64, 64)))
        # any batch in the same power-of-two bucket hits the record
        for b in (3, 4):
            assert autotune.best_blocks(b=b, **shape, **dt) \
                == (rec.bq, rec.bk), b
        # a different bucket INTERPOLATES from the tuned neighbor bucket
        # (PR 6: cross-shape generalization instead of default fallback)
        assert autotune.best_blocks(b=5, **shape, **dt) == (rec.bq, rec.bk)
        # ... but a shape with no tuned neighbor (different head dim:
        # never a neighbor axis) still gets the declared default
        assert autotune.best_blocks(b=5, h=4, kvh=2, sq=64, sk=64, dh=64,
                                    **dt) == autotune.DEFAULT_BLOCKS
    finally:
        registry.clear_tune_table()


def test_interpolation_prefers_exact_bucket_over_neighbor(tmp_path):
    """Cross-shape generalization parity: where BOTH the exact bucket
    and a neighbor bucket are tuned, ``best`` returns the exact bucket's
    winner; only untuned buckets adopt the nearest neighbor's."""
    registry.clear_tune_table()
    try:
        shape = dict(h=4, kvh=2, sq=64, sk=64, dh=32)
        dt = dict(dtype=jnp.float32, causal=True)
        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        # force DIFFERENT winners per bucket via disjoint candidate sets
        registry.autotune("attention", sess, b=2, **shape,
                          candidates=((64, 64),))
        registry.autotune("attention", sess, b=4, **shape,
                          candidates=((32, 32),))
        assert registry.best("attention", b=2, **shape, **dt) == (64, 64)
        assert registry.best("attention", b=4, **shape, **dt) == (32, 32)
        # untuned b=8 bucket: nearest-first neighbor order adopts b=4
        assert registry.best("attention", b=8, **shape, **dt) == (32, 32)
        # the adoption is recorded under the exact key as interpolated
        rec = [r for r in registry.dump_tune_table()["records"]
               if r["key"].startswith("b8")]
        assert rec and rec[0]["interpolated"] and not rec[0]["swept"]
    finally:
        registry.clear_tune_table()


def test_interpolation_vmem_gates_adopted_choice():
    """A neighbor's winner is only adopted when it fits the VMEM budget
    at the ACTUAL shape — oversized tilings fall through to default."""
    registry.clear_tune_table()
    try:
        from repro.core import hwinfo
        # large sq/sk: the vmem model clamps blocks to the sequence, so
        # only a long-sequence shape can actually bust the budget
        shape = dict(h=4, kvh=2, sq=1 << 15, sk=1 << 15, dh=32)
        dt = dict(dtype=jnp.float32, causal=True)
        key4 = registry.attention_tune_key(b=4, **shape, **dt)
        huge = (1 << 15, 1 << 15)
        assert registry.attention_vmem(*huge, shape["dh"]) \
            > hwinfo.device_chip().scoped_vmem_bytes * 0.9
        registry.record("attention", key4, huge)
        # b=8 interpolates from the b=4 bucket first, but the choice
        # busts the budget -> skipped -> declared default
        assert registry.best("attention", b=8, **shape, **dt) \
            == registry.DEFAULT_BLOCKS
        # a fitting neighbor IS adopted (sanity: gate, not a blanket no)
        registry.clear_tune_table()  # drop the gated record + markers
        fit = (64, 64)
        registry.record("attention", key4, fit)
        assert registry.best("attention", b=8, **shape, **dt) == fit
    finally:
        registry.clear_tune_table()


def test_stale_negative_cache_dropped_when_custom_root_registers():
    """Regression (PR 6): ``clear_tune_table()`` forgets custom cache
    roots; a ``best`` miss noted *before* a later autotune re-registers
    the root must not mask that root's on-disk record."""
    import tempfile
    registry.clear_tune_table()
    try:
        with tempfile.TemporaryDirectory() as root:
            sess = ProfileSession(cache_dir=root)
            rec = registry.autotune("stream_triad", sess, n=TRIAD_N,
                                    candidates=TRIAD_CANDS)
            # full clear: records AND learned roots are gone; dispatch
            # falls to the default and negative-caches the disk miss
            registry.clear_tune_table()
            assert registry.best("stream_triad", n=TRIAD_N) \
                == (registry.DEFAULT_BLOCK_ROWS,)
            # tuning a DIFFERENT shape through the same custom root
            # re-registers it — the stale miss for the first shape must
            # be dropped, so its persisted winner is visible again
            sess2 = ProfileSession(cache=ArtifactCache(root))
            registry.autotune("stream_triad", sess2, n=TRIAD_N * 2,
                              candidates=TRIAD_CANDS)
            assert registry.best("stream_triad", n=TRIAD_N) == rec.choice
    finally:
        registry.clear_tune_table()


def test_default_interpret_follows_the_backend_alone(monkeypatch):
    """The backend alone decides: a TPU always compiles its kernels."""
    assert registry.default_interpret("tpu") is False
    assert registry.default_interpret("cpu") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert registry.default_interpret() is False
