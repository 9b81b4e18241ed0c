"""Paged KV cache: kernel parity, dispatch rules, pool invariants, engine
token equivalence (kernels/paged_decode.py, serve/kv_pool.py).

The PR's acceptance surface: the Pallas paged kernel and the gather-based
jnp reference agree with the dense oracle across (page_size x ragged
lengths x GQA groups); the pool never double-allocates, never leaks, and
drains after a scheduler run; and a paged engine emits bit-identical
greedy tokens to the dense engine in fp32 — while its decode programs
touch O(context), not O(max_seq), bytes (asserted in
benchmarks/bench_paged_decode.py from artifact events).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifact_cache import ArtifactCache
from repro.core.session import ProfileSession
from repro.kernels import autotune, dispatch, ref, registry
from repro.kernels.paged_decode import (paged_decode_attention,
                                        paged_decode_attention_q8)
from repro.models.attention import paged_decode_jnp
from repro.serve.kv_pool import KVPool, pages_for


def _case(rng, b, h, kvh, dh, ps, np_w, lens):
    """Random pool + shuffled per-row page tables + a new token."""
    p_total = b * np_w + 1
    q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(p_total, ps, kvh, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p_total, ps, kvh, dh)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, 1, kvh, dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, 1, kvh, dh)), jnp.float32)
    ids = rng.permutation(np.arange(1, p_total))[:b * np_w].reshape(b, np_w)
    pt = jnp.asarray(ids, jnp.int32)
    return q, kp, vp, pt, jnp.asarray(lens, jnp.int32), kn, vn


# ---------------------------------------------------------------------------
# kernel parity grid: page_size x ragged lengths x GQA groups
# ---------------------------------------------------------------------------

def _run_flavor(flavor, q, kp, vp, pt, lens, kn, vn, *, ppb,
                ref_pt=None, ref_kp=None, ref_vp=None, scales=None):
    """(kernel output, oracle output) of one flavor: ``fp`` pages as given,
    ``bf16`` queries, pages and new token (the oracle reads the same
    values in f32), or ``q8``: int8 codes with per-token ``scales``.  The
    oracle may read other pages and tables than the kernel (``ref_*``)."""
    ref_pt = pt if ref_pt is None else ref_pt
    ref_kp = kp if ref_kp is None else ref_kp
    ref_vp = vp if ref_vp is None else ref_vp
    if flavor in ("fp", "bf16"):
        if flavor == "bf16":              # the oracle reads the same values
            q, kp, vp, kn, vn, ref_kp, ref_vp = (
                x.astype(jnp.bfloat16)
                for x in (q, kp, vp, kn, vn, ref_kp, ref_vp))
        f32 = lambda x: x.astype(jnp.float32)
        want = ref.paged_decode(f32(q), f32(ref_kp), f32(ref_vp), ref_pt,
                                lens, f32(kn), f32(vn))
        got = paged_decode_attention(q, kp, vp, pt, lens, kn, vn,
                                     pages_per_block=ppb, interpret=True)
        return f32(got), want
    ksc, vsc, ref_ksc, ref_vsc = scales
    want = ref.paged_decode_q8(q, ref_kp, ref_vp, ref_pt, lens, kn, vn,
                               k_scale=ref_ksc, v_scale=ref_vsc)
    got = paged_decode_attention_q8(q, kp, vp, pt, lens, kn, vn,
                                    k_scale=ksc, v_scale=vsc,
                                    pages_per_block=ppb, interpret=True)
    return got, want


def _tol(flavor):
    """f32 arithmetic throughout; a bf16 output is rounded once (2^-9)."""
    return (dict(rtol=2 ** -7, atol=2 ** -7) if flavor == "bf16"
            else dict(rtol=1e-5, atol=1e-5))


def _q8_pages(rng, shape):
    codes = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.005, 0.05, size=shape[:2]),
                        jnp.float32)
    return codes, scale


# a block of ppb pages: 3 and 4 do not divide widths 7 and 6
@pytest.mark.parametrize("ps,np_w,ppb", [(4, 7, 1), (8, 4, 2), (16, 3, 4),
                                         (4, 7, 3), (8, 6, 4)])
@pytest.mark.parametrize("h,kvh", [(4, 2), (8, 2), (4, 4), (7, 1)])
def test_paged_kernel_parity_grid(ps, np_w, ppb, h, kvh):
    rng = np.random.default_rng(ps * 100 + h * 10 + kvh + ppb * 1000)
    b, dh = 3, 16
    lens = [int(rng.integers(0, np_w * ps + 1)) for _ in range(b)]
    args = _case(rng, b, h, kvh, dh, ps, np_w, lens)
    want = ref.paged_decode(*args)
    got_k = paged_decode_attention(*args, pages_per_block=ppb,
                                   interpret=True)
    got_j = paged_decode_jnp(*args)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("flavor", ["fp", "bf16", "q8"])
@pytest.mark.parametrize("kvh", [2, 1])
@pytest.mark.parametrize("ppb", [1, 2, 3])
def test_paged_kernel_edge_rows(ppb, kvh, flavor):
    """Empty row (length 0, null-page table), a single token, exactly one
    block, the full table width and a block and a bit — in one batch,
    with blocks that do and do not divide the width."""
    rng = np.random.default_rng(7 + ppb)
    b, h, dh, ps, np_w = 5, 4, 16, 8, 7
    q, kp, vp, pt, _, kn, vn = _case(rng, b, h, kvh, dh, ps, np_w,
                                     [0] * b)
    pt = pt.at[0].set(0)                      # released slot: null pages
    lens = jnp.asarray([0, 1, ppb * ps, np_w * ps, ppb * ps + 3],
                       jnp.int32)
    scales = None
    if flavor == "q8":
        kp, ksc = _q8_pages(rng, kp.shape)
        vp, vsc = _q8_pages(rng, vp.shape)
        scales = (ksc, vsc, ksc, vsc)
    got, want = _run_flavor(flavor, q, kp, vp, pt, lens, kn, vn,
                            ppb=ppb, scales=scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(flavor))
    # the empty row attends only the new token: output is exactly v_new
    got0 = np.asarray(got[0, 0]).reshape(kvh, h // kvh, dh)
    np.testing.assert_allclose(
        got0, np.broadcast_to(np.asarray(vn[0, 0])[:, None], got0.shape),
        **_tol(flavor))


@pytest.mark.parametrize("flavor", ["fp", "bf16", "q8"])
@pytest.mark.parametrize("ppb", [2, 4])
def test_paged_kernel_never_reads_dead_pages(ppb, flavor):
    """Every table entry past a row's live pages points at a page of NaN,
    and every token past a row's length in its last live page is NaN: the
    output still equals the oracle run over clean pages, so no dead page
    and no dead token reaches the softmax or the P.V product."""
    rng = np.random.default_rng(11 + ppb)
    b, h, kvh, dh, ps, np_w = 4, 4, 2, 16, 4, 9
    lens = np.array([0, 5, ppb * ps, np_w * ps - 2])
    q, kp, vp, pt, lens_j, kn, vn = _case(rng, b, h, kvh, dh, ps, np_w,
                                          lens)
    if flavor == "q8":
        kp, ksc = _q8_pages(rng, kp.shape)
        vp, vsc = _q8_pages(rng, vp.shape)
    nan_page = kp.shape[0]                    # one page past the pool
    live = -(-lens // ps)
    dead = np.arange(np_w)[None, :] >= live[:, None]
    pt_nan = jnp.where(jnp.asarray(dead), nan_page, pt)
    # token rows of live pages past each row's length
    tail = np.zeros(kp.shape[:2], bool)
    ptn = np.asarray(pt)
    for r in range(b):
        for j in range(live[r]):
            tail[ptn[r, j], max(lens[r] - j * ps, 0):] = True
    tail = jnp.asarray(tail)
    if flavor != "q8":
        poison = lambda x: jnp.concatenate(
            [jnp.where(tail[..., None, None], jnp.nan, x),
             jnp.full_like(x[:1], jnp.nan)])
        got, want = _run_flavor(flavor, q, poison(kp), poison(vp),
                                pt_nan, lens_j, kn, vn, ppb=ppb,
                                ref_pt=pt, ref_kp=kp, ref_vp=vp)
    else:
        poison = lambda sc: jnp.concatenate(
            [jnp.where(tail, jnp.nan, sc), jnp.full_like(sc[:1], jnp.nan)])
        pad = lambda x: jnp.concatenate([x, x[:1]])
        got, want = _run_flavor(flavor, q, pad(kp), pad(vp), pt_nan,
                                lens_j, kn, vn, ppb=ppb, ref_pt=pt,
                                ref_kp=kp, ref_vp=vp,
                                scales=(poison(ksc), poison(vsc), ksc, vsc))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(flavor))


def test_paged_matches_dense_decode_token_softmax():
    """The jnp paged reference must agree with the DENSE two-part softmax
    run over the same logical context (the masked-dense oracle bar)."""
    from repro.models.attention import _decode_token_attend
    rng = np.random.default_rng(3)
    b, h, kvh, dh, ps, np_w = 2, 4, 2, 16, 8, 4
    lens = [19, 7]
    q, kp, vp, pt, lens_j, kn, vn = _case(rng, b, h, kvh, dh, ps, np_w, lens)
    got = paged_decode_jnp(q, kp, vp, pt, lens_j, kn, vn)
    # densify: gather each row's pages into a contiguous cache
    k_ctx = np.asarray(kp)[np.asarray(pt)].reshape(b, np_w * ps, kvh, dh)
    v_ctx = np.asarray(vp)[np.asarray(pt)].reshape(b, np_w * ps, kvh, dh)
    valid = jnp.arange(np_w * ps)[None, :] < lens_j[:, None]
    want = _decode_token_attend(q, jnp.asarray(k_ctx), jnp.asarray(v_ctx),
                                valid, kn, vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# dispatch: the override ladder reaches the paged impls
# ---------------------------------------------------------------------------

def test_paged_dispatch_override_ladder(monkeypatch):
    assert dispatch.select_paged_decode_impl(backend="tpu") == "pallas_paged"
    assert dispatch.select_paged_decode_impl(backend="cpu") == "jnp_paged"
    with dispatch.use_attention_impl("paged_decode"):
        assert dispatch.select_paged_decode_impl(backend="cpu") \
            == "pallas_paged"
        # paged_decode is transparent to prefill selection
        assert dispatch.select_attention_impl(sq=256, sk=256, dh=64,
                                              backend="cpu") == "full"
    with dispatch.use_attention_impl("full"):
        assert dispatch.select_paged_decode_impl(backend="tpu") == "jnp_paged"
    with dispatch.use_attention_impl("pallas_flash"):
        assert dispatch.select_paged_decode_impl(backend="cpu") \
            == "pallas_paged"
    monkeypatch.setenv("REPRO_ATTN_IMPL", "paged_decode")
    assert dispatch.select_paged_decode_impl(backend="cpu") == "pallas_paged"


def test_run_attention_rejects_paged_decode():
    x = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="decode-attention impl"):
        dispatch.run_attention("paged_decode", x, x[:, :, :1], x[:, :, :1])
    with pytest.raises(ValueError):
        dispatch.run_paged_decode("nope", x, x, x, x, x, x, x)


def test_run_paged_decode_impls_agree():
    rng = np.random.default_rng(11)
    args = _case(rng, 2, 4, 2, 16, 8, 3, [17, 5])
    want = ref.paged_decode(*args)
    for name in dispatch.PAGED_DECODE_IMPLS:
        got = dispatch.run_paged_decode(name, *args, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# autotune: (page_size x pages_per_block) through the session
# ---------------------------------------------------------------------------

PAGED_SHAPE = dict(b=2, kvh=2, g=2, dh=16, ctx=64)
PAGED_CANDS = ((16, 1), (16, 2), (32, 1))


def test_paged_autotune_cold_warm_zero_lowerings(tmp_path):
    cold = ProfileSession(cache_dir=str(tmp_path / "cache"))
    rec = autotune.autotune_paged_decode(**PAGED_SHAPE, session=cold,
                                         candidates=PAGED_CANDS)
    assert rec.lowerings == len(PAGED_CANDS) == cold.lowerings
    assert (rec.page_size, rec.pages_per_block) in PAGED_CANDS
    warm = ProfileSession(cache=ArtifactCache(str(tmp_path / "cache")))
    rec2 = autotune.autotune_paged_decode(**PAGED_SHAPE, session=warm,
                                          candidates=PAGED_CANDS)
    assert warm.lowerings == 0                 # the acceptance criterion
    assert (rec2.page_size, rec2.pages_per_block) == \
        (rec.page_size, rec.pages_per_block)
    assert rec2.scores == rec.scores


def test_paged_autotune_feeds_dispatch_table(tmp_path):
    autotune.clear_table()
    try:
        kw = dict(b=2, kvh=2, g=2, dh=16, page_size=16, dtype=jnp.float32)
        assert autotune.best_paged_block(**kw) \
            == registry.default_pages_per_block(16)
        sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
        rec = autotune.autotune_paged_decode(**PAGED_SHAPE, session=sess,
                                             candidates=PAGED_CANDS)
        # the winner per page_size is consulted by dispatch — and the key
        # buckets the context, so the scheduler's live-mix widths (here
        # 48 tokens of table against the sweep's 64) find the same record
        by_ppb = {ppb: s for (ps, ppb), s in rec.scores.items()
                  if ps == 16}
        got = registry.best("paged_decode", ctx=48, **kw)[1]
        assert by_ppb[got] == min(by_ppb.values())
    finally:
        autotune.clear_table()


def test_paged_autotune_vmem_gate(tmp_path):
    sess = ProfileSession(cache_dir=str(tmp_path / "cache"))
    rec = autotune.autotune_paged_decode(
        **PAGED_SHAPE, session=sess, candidates=((16, 1), (64, 4)),
        vmem_fraction=0.02)
    assert rec.scores[(64, 4)] == float("inf")   # gated, never lowered
    assert sess.lowerings == 1
    with pytest.raises(ValueError):
        autotune.autotune_paged_decode(**PAGED_SHAPE, session=sess,
                                       candidates=((64, 4),),
                                       vmem_fraction=1e-7)


# ---------------------------------------------------------------------------
# the pool: no double-alloc, no leaks, churn-proof
# ---------------------------------------------------------------------------

def test_pool_alloc_release_invariants():
    pool = KVPool(num_pages=17, page_size=8, slots=3, table_width=5)
    pool.check()
    assert pool.available() == 16
    assert pool.alloc(0, 20) == pages_for(20, 8) == 3
    assert pool.alloc(1, 8) == 1
    pool.check()
    # growth is incremental: covering 22 tokens from 20 adds nothing new,
    # crossing the boundary adds exactly one page
    assert pool.ensure(0, 24) == 0
    assert pool.ensure(0, 25) == 1
    pool.check()
    assert pool.slot_pages(0) == 4 and pool.slot_pages(1) == 1
    # tables list the owned pages then zeros (null page)
    assert (pool.tables[0, :4] > 0).all() and pool.tables[0, 4] == 0
    assert pool.release(0) == 4
    pool.check()
    assert pool.release(0) == 0          # idempotent, no double-free
    assert pool.release(1) == 1
    pool.check()
    assert pool.all_free()


def test_pool_reservation_gates_future_growth():
    """can_reserve accounts for pages already PROMISED to active slots,
    not just currently-free ones — the guarantee that decode growth
    never fails mid-run."""
    pool = KVPool(num_pages=9, page_size=8, slots=2, table_width=5)
    pool.reserve(0, 32)                      # promise 4 pages
    pool.alloc(0, 8)                         # but only 1 allocated yet
    assert pool.available() == 7
    assert pool.unpromised() == 4            # 3 are spoken for
    assert pool.can_reserve(32)              # 4 <= 4
    assert not pool.can_reserve(33)          # 5 > 4
    # growth up to the reservation always succeeds
    pool.ensure(0, 32)
    pool.check()
    pool.release(0)
    assert pool.unpromised() == 8


@pytest.mark.slow
def test_scheduler_small_pool_defers_instead_of_aborting():
    """A pool sized well below the dense worst case must serve every
    request by deferring admissions — never by raising mid-decode (the
    failure mode reservation-gated admission exists to prevent)."""
    from repro.serve.engine import (BatchScheduler, Engine, Request,
                                    ServeConfig)
    lm, params = _lm_params()
    # room for roughly one worst-case request at a time
    eng = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=3,
                                         page_size=4, pool_pages=14,
                                         admission_chunk=4))
    dense = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=3))
    sched = BatchScheduler(eng)
    prompts = {rid: [rid + 1, rid + 2] for rid in range(4)}
    for rid, p in prompts.items():
        sched.submit(Request(rid=rid, prompt=p, max_new_tokens=20))
    done = sched.run()                       # must not raise
    assert set(done) == set(prompts)
    for rid, p in prompts.items():
        assert done[rid].generated == \
            dense.generate([p], max_new_tokens=20)[0]
    sched.pool.check()
    assert sched.pool.all_free()


def test_pool_exhaustion_and_overflow_errors():
    pool = KVPool(num_pages=4, page_size=8, slots=2, table_width=2)
    assert pool.can_fit(16, 0)
    pool.alloc(0, 16)
    assert not pool.can_fit(16, 1)           # only 1 page left
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1, 16)
    with pytest.raises(ValueError, match="table_width"):
        pool.ensure(0, 8 * 3)                # 3 pages > table_width 2
    with pytest.raises(ValueError, match="null page"):
        KVPool(num_pages=1, page_size=8, slots=1, table_width=1)


def test_pool_churn_is_leak_free():
    rng = np.random.default_rng(0)
    pool = KVPool(num_pages=33, page_size=4, slots=4, table_width=8)
    lens = [0] * 4
    for step in range(200):
        slot = int(rng.integers(0, 4))
        if lens[slot] and rng.random() < 0.4:
            pool.release(slot)
            lens[slot] = 0
        else:
            want = min(int(lens[slot] + rng.integers(1, 9)), 32)
            if pool.can_fit(want, slot):
                pool.ensure(slot, want)
                lens[slot] = want
        pool.check()                          # every invariant, every step
    for slot in range(4):
        pool.release(slot)
    pool.check()
    assert pool.all_free()


# ---------------------------------------------------------------------------
# engine: paged == dense tokens (fp32 greedy), pool drains after run()
# ---------------------------------------------------------------------------

def _lm_params():
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    cfg = LMConfig(name="t", family="dense", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)
    lm = LM(cfg, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0))


def test_set_page_table_empties_rows_without_pages():
    """A slot whose table row starts at the null page owns no pages: the
    table swap sets its length to 0, so the kernel walks nothing for it,
    while rows with pages keep their lengths."""
    from repro.serve.engine import Engine, ServeConfig
    lm, params = _lm_params()
    eng = Engine(lm, params, ServeConfig(max_seq=32, batch_slots=3,
                                         page_size=8))
    state = eng.lm.init_decode_state(3, 32, **eng._state_kwargs())
    state = eng._with_lengths(state, jnp.asarray([5, 9, 7], jnp.int32))
    table = np.zeros((3, eng.table_width), np.int32)
    table[0, :1] = [3]
    table[2, :2] = [1, 2]
    got = eng.set_page_table(state, table)["caches"].length
    assert got.shape == state["caches"].length.shape
    np.testing.assert_array_equal(np.asarray(got),
                                  np.broadcast_to([5, 0, 7], got.shape))


def test_engine_rejects_paged_for_recurrent_families():
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    from repro.serve.engine import Engine, ServeConfig
    cfg = LMConfig(name="t", family="xlstm", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=4, d_ff=64)
    lm = LM(cfg, default_features().with_(remat_policy="none"))
    with pytest.raises(ValueError, match="attention-cache"):
        Engine(lm, None, ServeConfig(max_seq=64, page_size=8))


def test_engine_rejects_paged_pin_on_dense_engine():
    """attn_impl="paged_decode" with page_size=0 would silently measure
    the dense path — the engine refuses the combination instead."""
    lm, params = _lm_params()
    from repro.serve.engine import Engine, ServeConfig
    with pytest.raises(ValueError, match="page_size"):
        Engine(lm, params, ServeConfig(max_seq=64,
                                       attn_impl="paged_decode"))


@pytest.mark.slow
def test_paged_generate_matches_dense_ragged():
    from repro.serve.engine import Engine, ServeConfig
    lm, params = _lm_params()
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7]]
    dense = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=4))
    paged = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=4,
                                           page_size=8))
    want = dense.generate(prompts, max_new_tokens=8)
    got = paged.generate(prompts, max_new_tokens=8)
    assert got == want                       # bit-identical greedy in fp32


@pytest.mark.slow
def test_paged_scheduler_matches_dense_and_drains_pool():
    """Scheduler churn (ragged budgets, slot reuse, mid-flight admission)
    over the pool: deterministic tokens vs the dense engine, no leaked or
    double-freed pages after run()."""
    from repro.serve.engine import (BatchScheduler, Engine, Request,
                                    ServeConfig)
    lm, params = _lm_params()
    dense = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=4))
    eng = Engine(lm, params, ServeConfig(max_seq=64, batch_slots=2,
                                         page_size=4, admission_chunk=4))
    sched = BatchScheduler(eng)
    budgets = {0: 3, 1: 7, 2: 5, 3: 2, 4: 6}
    prompts = {rid: [rid + 1, rid + 2, rid + 3][:(rid % 3) + 1]
               for rid in budgets}
    for rid, budget in budgets.items():
        sched.submit(Request(rid=rid, prompt=prompts[rid],
                             max_new_tokens=budget))
    done = sched.run()
    assert set(done) == set(budgets)
    for rid, budget in budgets.items():
        want = dense.generate([prompts[rid]], max_new_tokens=budget)[0]
        assert done[rid].generated == want, rid
        assert len(done[rid].generated) == budget   # overshoot masked
    sched.pool.check()
    assert sched.pool.all_free(), sched.pool
    assert sched.pool.allocs == sched.pool.releases > 0


@pytest.mark.slow
def test_paged_engine_through_pallas_kernel():
    """attn_impl="paged_decode" pins the Pallas paged kernel for every
    decode the engine traces — tokens stay identical to the dense path."""
    from repro.serve.engine import Engine, ServeConfig
    lm, params = _lm_params()
    prompts = [[3, 1, 4], [9, 2]]
    dense = Engine(lm, params, ServeConfig(max_seq=32, batch_slots=2))
    want = dense.generate(prompts, max_new_tokens=4)
    eng = Engine(lm, params, ServeConfig(max_seq=32, batch_slots=2,
                                         page_size=8,
                                         attn_impl="paged_decode"))
    got = eng.generate(prompts, max_new_tokens=4)
    assert got == want
