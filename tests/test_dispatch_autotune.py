"""Kernel dispatch layer + flash block autotuner (kernels/dispatch.py,
kernels/autotune.py) and the serving wiring on top of them.

The PR's acceptance surface: implementation selection is static and
overridable, every named impl agrees numerically, `Engine.generate` emits
bit-identical tokens whichever impl prefills, and a warm rerun of the
autotune sweep performs zero lowerings.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifact_cache import ArtifactCache
from repro.core.session import ProfileSession
from repro.kernels import autotune, dispatch, ref


# ---------------------------------------------------------------------------
# selection: static facts only, override beats heuristics
# ---------------------------------------------------------------------------

def test_select_backend_rules():
    kw = dict(sq=256, sk=256, dh=64)
    assert dispatch.select_attention_impl(**kw, backend="tpu") \
        == "pallas_flash"
    assert dispatch.select_attention_impl(sq=4, sk=4, dh=64,
                                          backend="tpu") == "full"
    assert dispatch.select_attention_impl(sq=256, sk=256, dh=31,
                                          backend="tpu") == "full"
    assert dispatch.select_attention_impl(**kw, backend="cpu") == "full"
    assert dispatch.select_attention_impl(**kw, backend="cpu",
                                          flash_min_seq=128) == "jnp_flash"
    assert dispatch.select_attention_impl(**kw, backend="cpu",
                                          flash_min_seq=512) == "full"


def test_select_differentiable_pins_the_vjp_twin():
    # the Pallas kernel is forward-only; grad paths stay on the twin
    assert dispatch.select_attention_impl(sq=256, sk=256, dh=64,
                                          backend="tpu",
                                          differentiable=True) == "jnp_flash"


def test_select_override_context_and_env(monkeypatch):
    kw = dict(sq=256, sk=256, dh=64, backend="cpu")
    with dispatch.use_attention_impl("pallas_flash"):
        assert dispatch.select_attention_impl(**kw) == "pallas_flash"
        # context override beats even the differentiable pin
        assert dispatch.select_attention_impl(
            **kw, differentiable=True) == "pallas_flash"
    assert dispatch.select_attention_impl(**kw) == "full"   # restored
    monkeypatch.setenv("REPRO_ATTN_IMPL", "jnp_flash")
    assert dispatch.select_attention_impl(**kw) == "jnp_flash"
    monkeypatch.setenv("REPRO_ATTN_IMPL", "bogus")
    with pytest.raises(ValueError):
        dispatch.select_attention_impl(**kw)


def test_use_attention_impl_rejects_unknown_and_none_is_noop():
    with pytest.raises(ValueError):
        with dispatch.use_attention_impl("nope"):
            pass
    with dispatch.use_attention_impl(None):
        assert dispatch.attention_impl_override() is None


def test_run_attention_unknown_impl_raises():
    x = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError):
        dispatch.run_attention("nope", x, x[:, :, :1], x[:, :, :1])


# ---------------------------------------------------------------------------
# all named impls agree on the serving shapes (offset + ragged + GQA)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", dispatch.ATTENTION_IMPLS)
def test_named_impls_match_oracle(name):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 112, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 112, 2, 32), jnp.float32)
    kv_len = jnp.array([112, 53], jnp.int32)
    want = ref.flash_attention(q, k, v, causal=True, q_offset=64,
                               kv_valid=kv_len)
    got = dispatch.run_attention(name, q, k, v, q_offset=64, causal=True,
                                 kv_len=kv_len, interpret=True,
                                 blocks=(32, 32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_long_prefill_keeps_q_chunked_memory_guard():
    """Above chunk_threshold on a jnp backend, prefill selects the flash
    twin but still runs it q-chunk by q-chunk (the 32k-prefill memory
    bound) — and matches the naive small-threshold path exactly."""
    from repro.models.attention import (AttnConfig, init_attn, init_kv_cache,
                                        prefill_into_cache)

    cfg = AttnConfig(d_model=32, num_heads=4, num_kv_heads=2, head_dim=16,
                     chunk_size=32, chunk_threshold=48)
    p = init_attn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 32), jnp.float32)
    lengths = jnp.array([96, 61], jnp.int32)
    assert dispatch.select_attention_impl(
        sq=96, sk=96, dh=16, flash_min_seq=48) == "jnp_flash"
    cache = init_kv_cache(2, 96, cfg, jnp.float32)
    got, got_cache = prefill_into_cache(p, x, cfg, cache, lengths=lengths)
    naive = cfg._replace(chunk_threshold=4096)     # full-attention baseline
    want, want_cache = prefill_into_cache(p, x, naive, cache,
                                          lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_cache.k),
                               np.asarray(want_cache.k), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# engine: same tokens whichever impl prefills (the dispatch-switch bar)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_generate_bit_identical_across_impls():
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    from repro.serve.engine import Engine, ServeConfig

    cfg = LMConfig(name="t", family="dense", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)
    # fp32: greedy argmax ties are then identical across softmax algorithms
    lm = LM(cfg, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7]]
    outs = {}
    for impl in (None, "jnp_flash", "pallas_flash"):
        eng = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=4,
                                             attn_impl=impl))
        outs[impl] = eng.generate(prompts, max_new_tokens=8)
    assert outs[None] == outs["jnp_flash"] == outs["pallas_flash"]


@pytest.mark.slow
def test_scheduler_prefills_through_pallas_kernel():
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    from repro.serve.engine import (BatchScheduler, Engine, Request,
                                    ServeConfig)

    cfg = LMConfig(name="t", family="dense", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)
    lm = LM(cfg, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0))
    base = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=4))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7]]
    want = base.generate(prompts, max_new_tokens=4)
    eng = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=2,
                                         attn_impl="pallas_flash",
                                         admission_chunk=2))
    sched = BatchScheduler(eng)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=4))
    done = sched.run()
    assert [done[r].generated for r in range(3)] == want


# ---------------------------------------------------------------------------
# autotuner: measured through the session, warm rerun is free
# ---------------------------------------------------------------------------

SHAPE = dict(b=1, h=4, kvh=2, sq=128, sk=128, dh=32)
CANDS = ((32, 32), (64, 64), (64, 128))


def test_autotune_cold_then_warm_zero_lowerings(tmp_path):
    cold = ProfileSession(cache_dir=str(tmp_path / "cache"))
    rec = autotune.autotune_flash_blocks(**SHAPE, session=cold,
                                         candidates=CANDS)
    assert rec.lowerings == len(CANDS) == cold.lowerings
    assert (rec.bq, rec.bk) in CANDS
    assert all(s > 0 for s in rec.scores.values())

    warm = ProfileSession(cache=ArtifactCache(str(tmp_path / "cache")))
    rec2 = autotune.autotune_flash_blocks(**SHAPE, session=warm,
                                          candidates=CANDS)
    assert warm.lowerings == 0                 # the acceptance criterion
    assert (rec2.bq, rec2.bk) == (rec.bq, rec.bk)
    assert rec2.scores == rec.scores


def test_autotune_feeds_dispatch_best_blocks(tmp_path):
    autotune.clear_table()
    try:
        dt = dict(dtype=jnp.float32, causal=True)
        assert autotune.best_blocks(**SHAPE, **dt) == autotune.DEFAULT_BLOCKS
        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        rec = autotune.autotune_flash_blocks(**SHAPE, session=sess,
                                             candidates=CANDS)
        assert autotune.best_blocks(**SHAPE, **dt) == (rec.bq, rec.bk)
        # a different shape still gets the default
        other = dict(SHAPE, sq=256)
        assert autotune.best_blocks(**other, **dt) == autotune.DEFAULT_BLOCKS
    finally:
        autotune.clear_table()


def test_autotune_vmem_gate_skips_oversized_tiles(tmp_path):
    sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
    # shrink the budget so (64,64) fits and (128,128) doesn't: the gated
    # candidate must be scored inf WITHOUT any XLA work
    rec = autotune.autotune_flash_blocks(
        **SHAPE, session=sess, candidates=((64, 64), (128, 128)),
        vmem_fraction=0.008)
    assert rec.scores[(128, 128)] == float("inf")     # gated, never lowered
    assert (rec.bq, rec.bk) == (64, 64)
    assert sess.lowerings == 1


def test_autotune_no_fitting_candidate_raises(tmp_path):
    sess = ProfileSession(cache_dir=str(tmp_path / "cache"), enabled=False)
    with pytest.raises(ValueError):
        autotune.autotune_flash_blocks(**SHAPE, session=sess,
                                       candidates=((64, 64),),
                                       vmem_fraction=1e-7)
