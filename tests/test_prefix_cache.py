"""Shared-prefix radix cache + int8 KV pages (serve/kv_pool.py,
kernels/paged_decode.py q8 path, engine admission).

The PR's acceptance surface: the refcounted trie maps shared prompt
prefixes read-only and copy-on-writes in-page forks, with every pool
invariant (refcount = slot refs + index ref, no leak, no double-free,
trie linkage) holding under 300 steps of randomized admit/fork/grow/
retire churn; the int8 paged kernels match the quantized dense oracle
across (page_size x ragged lengths x GQA); and a prefix-cached engine
emits bit-identical greedy tokens to the uncached run — in fp32 exactly,
and per-dtype deterministically for int8 pages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, registry
from repro.kernels.paged_decode import paged_decode_attention_q8
from repro.models.attention import paged_decode_jnp
from repro.serve.kv_pool import KVPool, pages_for

# ---------------------------------------------------------------------------
# pool: trie admission semantics
# ---------------------------------------------------------------------------


def _admit(pool, slot, prompt, worst_extra=8):
    """The scheduler's admission protocol, condensed."""
    worst = len(prompt) + worst_extra
    _, shared = pool.match_prefix(prompt)
    if not pool.can_reserve(worst, shared_pages=shared):
        return None
    admit = pool.admit_prefix(slot, prompt)
    pool.reserve(slot, worst)
    pool.alloc(slot, len(prompt))
    pool.register_prefix(slot, prompt)
    return admit


def test_admit_prefix_full_match_maps_pages_read_only():
    pool = KVPool(num_pages=32, page_size=4, slots=4, table_width=8)
    p0 = list(range(10, 23))                    # 13 tokens: 3 full pages
    admit = _admit(pool, 0, p0)
    assert admit.matched_len == 0 and admit.cow is None
    assert pool.index_pages() == 3              # full pages indexed
    # identical prompt: all 3 full pages hit (usable prefix = 12 tokens)
    assert pool.match_prefix(p0) == (12, 3)
    admit = _admit(pool, 1, p0)
    assert (admit.matched_len, admit.shared_full) == (12, 3)
    assert admit.cow is None                    # match ends on a boundary
    # both slots map the SAME physical pages for the shared span
    assert pool.owned[0][:3] == pool.owned[1][:3]
    assert pool.shared_page_refs() == 3
    for pid in pool.owned[0][:3]:
        assert pool.refcnt[pid] == 3            # 2 slots + trie
    pool.check()


def test_admit_prefix_in_page_fork_cows():
    pool = KVPool(num_pages=32, page_size=4, slots=4, table_width=8)
    p0 = list(range(10, 23))
    _admit(pool, 0, p0)
    fork = p0[:6] + [99, 98, 97, 96]            # diverges inside page 1
    admit = _admit(pool, 1, fork)
    assert admit.matched_len == 6 and admit.shared_full == 1
    src, dst = admit.cow
    assert src == pool.owned[0][1]              # fork page of the donor
    assert dst == pool.owned[1][1]              # private copy, fresh page
    assert src != dst
    assert pool.owned[0][0] == pool.owned[1][0]  # full page still shared
    assert pool.cow_copies == 1
    pool.check()


def test_release_retains_index_pages_for_future_hits():
    pool = KVPool(num_pages=32, page_size=4, slots=2, table_width=8)
    p0 = list(range(10, 22))                    # 12 tokens: 3 full pages
    _admit(pool, 0, p0)
    pool.release(0)
    pool.check()
    assert not pool.all_free()                  # trie kept the pages
    assert pool.index_pages() == 3
    assert pool.reclaimable() == pool.num_pages - 1
    # a new admission of the same prompt hits the retired prompt's pages
    admit = _admit(pool, 1, p0)
    assert (admit.matched_len, admit.shared_full) == (11, 2)
    pool.check()


def test_index_only_pages_evict_lru_leaf_first_under_pressure():
    pool = KVPool(num_pages=10, page_size=4, slots=2, table_width=8)
    p0 = [1] * 8 + [2] * 4                      # 3 full pages
    _admit(pool, 0, p0, worst_extra=0)
    pool.release(0)
    assert pool.index_pages() == 3
    # 9 usable pages, 3 index-only: a 28-token admission must evict
    big = [int(t) for t in range(3, 31)]
    admit = _admit(pool, 1, big, worst_extra=0)
    assert admit is not None                    # evictables count as capacity
    assert pool.evictions > 0
    pool.check()
    # leaves evict before parents: whatever index remains is a valid chain
    pool.release(1)
    pool.check()


def test_clear_index_frees_everything():
    pool = KVPool(num_pages=32, page_size=4, slots=2, table_width=8)
    _admit(pool, 0, list(range(10, 22)))
    pool.release(0)
    assert pool.index_pages() > 0
    freed = pool.clear_index()
    assert freed == 3 and pool.all_free()
    pool.check()


def test_prefix_cache_off_is_inert():
    pool = KVPool(num_pages=32, page_size=4, slots=2, table_width=8,
                  prefix_cache=False)
    p0 = list(range(10, 22))
    _admit(pool, 0, p0)
    assert pool.match_prefix(p0) == (0, 0)
    assert pool.index_pages() == 0
    pool.release(0)
    assert pool.all_free()
    pool.check()


def test_can_reserve_counts_shared_pages_as_capacity():
    pool = KVPool(num_pages=9, page_size=4, slots=2, table_width=8)
    p0 = list(range(10, 26))                    # 16 tokens: 4 pages
    _admit(pool, 0, p0, worst_extra=0)
    # 4 pages free of 8: a fresh 16-token prompt can't reserve...
    assert not pool.can_reserve(17)
    # ...but the SAME prompt shares 3 full pages, so it can
    assert pool.match_prefix(p0)[1] == 3
    assert pool.can_reserve(17, shared_pages=3)
    pool.check()


# ---------------------------------------------------------------------------
# pool: randomized churn (admit / fork / grow / retire), invariants each step
# ---------------------------------------------------------------------------

def test_pool_prefix_churn_invariants():
    rng = np.random.default_rng(1234)
    ps, slots = 4, 4
    pool = KVPool(num_pages=24, page_size=ps, slots=slots, table_width=10)
    lens = [0] * slots
    history = []                                 # prompts to fork from
    admitted = deferred = 0
    for _ in range(300):
        slot = int(rng.integers(0, slots))
        if lens[slot] == 0:
            if history and rng.random() < 0.6:   # fork a previous prompt
                base = history[int(rng.integers(0, len(history)))]
                cut = int(rng.integers(0, len(base) + 1))
                tail = rng.integers(1, 6, size=int(rng.integers(1, 12)))
                prompt = base[:cut] + [int(t) for t in tail]
            else:
                toks = rng.integers(1, 6, size=int(rng.integers(1, 24)))
                prompt = [int(t) for t in toks]
            worst = len(prompt) + int(rng.integers(1, 12))
            _, shared = pool.match_prefix(prompt)
            if not pool.can_reserve(worst, shared_pages=shared):
                deferred += 1                    # backpressure, not a crash
            else:
                admit = pool.admit_prefix(slot, prompt)
                assert admit.matched_len < len(prompt)
                pool.reserve(slot, worst)
                pool.alloc(slot, len(prompt))
                pool.register_prefix(slot, prompt)
                lens[slot] = len(prompt)
                history = (history + [prompt])[-12:]
                admitted += 1
        elif rng.random() < 0.35:
            pool.release(slot)
            lens[slot] = 0
        else:
            # grow within the reservation: guaranteed to succeed
            cap = pool.reserved[slot] * ps
            want = min(lens[slot] + int(rng.integers(1, 6)), cap)
            pool.ensure(slot, want)
            lens[slot] = max(lens[slot], want)
        pool.check()                             # every invariant, every step
    for slot in range(slots):
        if lens[slot]:
            pool.release(slot)
    pool.check()
    assert pool.allocs == pool.releases > 0
    assert pool.reclaimable() == pool.num_pages - 1   # free or index-only
    assert admitted > 50 and deferred > 0 and pool.evictions > 0
    assert pool.prefix_hit_tokens > 0 and pool.cow_copies > 0


# ---------------------------------------------------------------------------
# int8 kernels: parity grid vs the quantized dense oracle
# ---------------------------------------------------------------------------

def _q8_case(rng, b, h, kvh, dh, ps, np_w, lens):
    p_total = b * np_w + 1
    q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), jnp.float32)
    # one layer's pool, stacked as the decode state stacks it
    kp = jnp.asarray(rng.integers(-127, 128, size=(1, p_total, ps, kvh * dh)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(1, p_total, ps, kvh * dh)),
                     jnp.int8)
    ksc = jnp.asarray(rng.uniform(0.005, 0.05, size=(1, p_total, ps)),
                      jnp.float32)
    vsc = jnp.asarray(rng.uniform(0.005, 0.05, size=(1, p_total, ps)),
                      jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, 1, kvh, dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, 1, kvh, dh)), jnp.float32)
    ids = rng.permutation(np.arange(1, p_total))[:b * np_w].reshape(b, np_w)
    pt = jnp.asarray(ids, jnp.int32)
    return q, kp, vp, pt, jnp.asarray(lens, jnp.int32), kn, vn, ksc, vsc


@pytest.mark.parametrize("ps,np_w,ppb", [(4, 7, 1), (8, 4, 2), (16, 3, 4)])
@pytest.mark.parametrize("h,kvh", [(4, 2), (8, 2), (4, 4)])
def test_q8_kernel_parity_grid(ps, np_w, ppb, h, kvh):
    rng = np.random.default_rng(ps * 100 + h * 10 + kvh)
    b, dh = 3, 16
    lens = [int(rng.integers(0, np_w * ps + 1)) for _ in range(b)]
    q, kp, vp, pt, lens_j, kn, vn, ksc, vsc = _q8_case(
        rng, b, h, kvh, dh, ps, np_w, lens)
    want = ref.paged_decode_q8(q, kp, vp, pt, lens_j, kn, vn,
                               k_scale=ksc, v_scale=vsc)
    got_k = paged_decode_attention_q8(q, kp, vp, pt, lens_j, kn, vn,
                                      k_scale=ksc, v_scale=vsc,
                                      pages_per_block=ppb, interpret=True)
    got_j = paged_decode_jnp(q, kp, vp, pt, lens_j, kn, vn,
                             k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_q8_registry_selection_and_supports():
    assert registry.select("paged_decode", quantized=True,
                           backend="cpu") == "jnp_paged_q8"
    assert registry.select("paged_decode", quantized=True,
                           backend="tpu") == "pallas_paged_q8"
    assert registry.select("paged_decode", backend="cpu") == "jnp_paged"
    # supports() partitions the family: fp impls refuse quantized facts
    for name in registry.impls("paged_decode"):
        spec = registry.get_spec("paged_decode", name)
        assert spec.supports(quantized=name.endswith("_q8"))
        assert not spec.supports(quantized=not name.endswith("_q8"))


def test_q8_registry_run_with_explicit_impl():
    rng = np.random.default_rng(5)
    q, kp, vp, pt, lens_j, kn, vn, ksc, vsc = _q8_case(
        rng, 2, 4, 2, 8, 4, 3, [7, 11])
    want = ref.paged_decode_q8(q, kp, vp, pt, lens_j, kn, vn,
                               k_scale=ksc, v_scale=vsc)
    got = registry.run("paged_decode", q, kp, vp, pt, lens_j, kn, vn,
                       impl="jnp_paged_q8", k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: suffix prefill + COW + int8, end to end
# ---------------------------------------------------------------------------

def _lm_params():
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    cfg = LMConfig(name="t", family="dense", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)
    lm = LM(cfg, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0))


def _sched_run(lm, params, prompts, max_new=4, **cfg_kw):
    from repro.serve.engine import (BatchScheduler, Engine, Request,
                                    ServeConfig)
    eng = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=2,
                                         page_size=8, admission_chunk=4,
                                         **cfg_kw))
    sched = BatchScheduler(eng)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=max_new))
    done = sched.run()
    sched.pool.check()
    return {r: done[r].generated for r in done}, sched


def _shared_prompts(rng, n=4, shared_len=20, tail=6):
    shared = [int(t) for t in rng.integers(1, 64, size=shared_len)]
    return [shared + [10 + i]
            + [int(t) for t in rng.integers(1, 64, size=tail - 1)]
            for i in range(n)]


@pytest.mark.slow
def test_prefix_cache_tokens_match_uncached_fp32():
    """Shared prompts ending mid-page: full-page sharing + COW forks +
    suffix prefill, all bit-identical to the uncached run (fp32 greedy)."""
    lm, params = _lm_params()
    prompts = _shared_prompts(np.random.default_rng(0))
    want, _ = _sched_run(lm, params, prompts, prefix_cache=False)
    got, sched = _sched_run(lm, params, prompts, prefix_cache=True)
    assert got == want
    m = sched.metrics
    assert m["prefix_hits"] == len(prompts) - 1
    assert m["cow_copies"] == len(prompts) - 1    # 20 % 8 != 0: in-page fork
    assert m["pages_shared"] == (len(prompts) - 1) * (20 // 8)
    assert m["prefilled_tokens"] < m["prompt_tokens"]
    assert sched.pool.allocs == sched.pool.releases
    assert sched.pool.reclaimable() == sched.pool.num_pages - 1


@pytest.mark.slow
def test_prefix_cache_aligned_prefix_skips_cow():
    """A page-aligned shared prefix maps read-only with NO copy."""
    lm, params = _lm_params()
    rng = np.random.default_rng(3)
    prompts = _shared_prompts(rng, shared_len=16, tail=8)  # 16 = 2 pages
    want, _ = _sched_run(lm, params, prompts, prefix_cache=False)
    got, sched = _sched_run(lm, params, prompts, prefix_cache=True)
    assert got == want
    assert sched.metrics["cow_copies"] == 0
    assert sched.metrics["pages_shared"] == (len(prompts) - 1) * 2


@pytest.mark.slow
def test_int8_engine_decodes_and_prefix_cache_composes():
    """int8 pages: generation runs end to end, the trie (token-keyed,
    dtype-blind) hits identically, and the cached int8 run is
    deterministic vs the uncached int8 run."""
    lm, params = _lm_params()
    prompts = _shared_prompts(np.random.default_rng(1))
    fp, sched_fp = _sched_run(lm, params, prompts, prefix_cache=True)
    q8_off, _ = _sched_run(lm, params, prompts, prefix_cache=False,
                           kv_dtype="int8")
    q8_on, sched_q8 = _sched_run(lm, params, prompts, prefix_cache=True,
                                 kv_dtype="int8")
    assert q8_on == q8_off                  # sharing changes no numerics
    assert all(len(t) == 4 for t in q8_on.values())
    assert (sched_q8.metrics["prefilled_tokens"]
            == sched_fp.metrics["prefilled_tokens"])


def test_engine_kv_dtype_validation():
    from repro.serve.engine import Engine, ServeConfig
    lm, params = _lm_params()
    with pytest.raises(ValueError, match="paged"):
        Engine(lm, params, ServeConfig(max_seq=64, kv_dtype="int8"))
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(lm, params, ServeConfig(max_seq=64, page_size=8,
                                       kv_dtype="fp8"))
    # an fp paged pin on an int8 engine is refused, naming the q8 impls
    with pytest.raises(ValueError, match="pallas_paged_q8"):
        Engine(lm, params, ServeConfig(max_seq=64, page_size=8,
                                       kv_dtype="int8",
                                       impls={"paged_decode":
                                              "pallas_paged"}))
    # and a q8 pin on an fp engine is refused the other way around
    with pytest.raises(ValueError, match="pallas_paged"):
        Engine(lm, params, ServeConfig(max_seq=64, page_size=8,
                                       impls={"paged_decode":
                                              "jnp_paged_q8"}))


def test_cli_kv_args_validate_eagerly():
    import argparse

    from repro.launch import cli
    ap = argparse.ArgumentParser()
    cli.add_kv_args(ap)
    args = ap.parse_args(["--kv-dtype", "int8"])
    with pytest.raises(ValueError, match="page-size"):
        cli.kv_config_kwargs(args)             # no --page-size: usage error
    args.page_size = 16
    kw = cli.kv_config_kwargs(args)
    assert kw == {"kv_dtype": "int8", "prefix_cache": True}
    args = ap.parse_args(["--no-prefix-cache"])
    args.page_size = 0
    assert cli.kv_config_kwargs(args) == {"kv_dtype": None,
                                          "prefix_cache": False}
