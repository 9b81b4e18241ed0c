"""Speculative decoding subsystem (serve/spec.py + the engine/scheduler
wiring): lossless greedy parity, rejection-sampling correctness, config
validation, and the two-namespace KV-pool closure under faults.

The load-bearing claims:

* greedy fp32 speculative tokens are BIT-identical to target-only decode
  — fused generate, streaming generate, and mixed spec/non-spec
  scheduler batches;
* the rejection policy's emitted token is distributed exactly as
  target-only sampling (checked against the target softmax on a seeded
  grid of trials);
* cancel/expire chaos against spec rows leaves the pool + scheduler
  invariant closure intact (draft-namespace pages released);
* snapshots refuse to restore under a different draft pairing, and
  restore under the SAME pairing reproduces the token stream.
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import default_features
from repro.models.lm import LM, LMConfig
from repro.serve import BatchScheduler, Engine, Request, ServeConfig
from repro.serve.spec import SpecConfig, accept_speculative

TCFG = LMConfig(name="spec-t", family="dense", vocab=256, d_model=64,
                n_layers=2, num_heads=8, num_kv_heads=4, d_ff=128)
DCFG = LMConfig(name="spec-d", family="dense", vocab=256, d_model=32,
                n_layers=1, num_heads=4, num_kv_heads=2, d_ff=64)
SCFG = ServeConfig(max_seq=128, batch_slots=4, temperature=0.0,
                   page_size=16, admission_chunk=8)
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7],
           [11, 12, 13, 14, 15, 16, 17, 18]]


@pytest.fixture(scope="module")
def models():
    feats = default_features().with_(remat_policy="none")
    lm = LM(TCFG, feats, dtype=jnp.float32)
    dlm = LM(DCFG, feats, dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0)), dlm.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def base_engine(models):
    lm, tp, _dp = models
    return Engine(lm, tp, SCFG)


@pytest.fixture(scope="module")
def ref_tokens(base_engine):
    return base_engine.generate(PROMPTS, max_new_tokens=24)


@pytest.fixture(scope="module")
def spec_engine(models):
    lm, tp, dp = models
    spec = SpecConfig(draft_config=DCFG, num_draft_tokens=4)
    return Engine(lm, tp, SCFG, spec=spec, draft_params=dp)


@pytest.fixture(scope="module")
def exact_spec_engine(models):
    """Draft == target (same config, same weights): greedy drafts verify,
    so rounds commit more than one token."""
    lm, tp, _dp = models
    spec = SpecConfig(draft_config=TCFG, num_draft_tokens=4)
    return Engine(lm, tp, SCFG, spec=spec, draft_params=tp)


# ---------------------------------------------------------------------------
# greedy parity: fused / streaming / scheduler
# ---------------------------------------------------------------------------

def test_fused_greedy_parity(spec_engine, ref_tokens):
    out = spec_engine.generate(PROMPTS, max_new_tokens=24)
    assert out == ref_tokens
    stats = spec_engine.spec_stats
    assert stats["proposed"] > 0 and 0.0 <= stats["accept_rate"] <= 1.0


def test_streaming_parity_and_callback_reconstruction(spec_engine,
                                                      exact_spec_engine,
                                                      ref_tokens):
    for eng in (spec_engine, exact_spec_engine):
        events = []
        out = eng.generate(
            PROMPTS, max_new_tokens=24,
            stream_cb=lambda i, toks, done: events.append(
                (i, list(toks), done)))
        assert out == ref_tokens
        rebuilt = [[] for _ in PROMPTS]
        for i, toks, _done in events:
            rebuilt[i].extend(toks)
        assert rebuilt == ref_tokens
        last = {i: done for i, _t, done in events}
        assert all(last[i] for i in range(len(PROMPTS)))
    # blockwise: a round streams its pending token plus every accepted
    # draft in ONE callback, so once drafts verify there are strictly
    # fewer callback waves than tokens.  (The independent random draft
    # above is never accepted under greedy, so it streams one token per
    # wave — which is why this is checked on the exact draft.)
    assert exact_spec_engine.spec_stats["accepted"] > 0
    assert len(events) < sum(len(t) for t in ref_tokens)


def test_scheduler_mixed_batch_parity(models, base_engine, spec_engine):
    def reqs():
        return [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=17,
                        spec=True),
                Request(rid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=11,
                        spec=False),
                Request(rid=2, prompt=[9, 8], max_new_tokens=23, spec=True),
                Request(rid=3, prompt=[4] * 12, max_new_tokens=9, spec=True),
                Request(rid=4, prompt=[17, 3, 2, 11], max_new_tokens=19,
                        spec=False),
                Request(rid=5, prompt=[30, 31], max_new_tokens=15,
                        spec=True)]

    s0 = BatchScheduler(base_engine)
    for r in reqs():
        s0.submit(r)
    ref = {rid: list(r.generated) for rid, r in s0.run().items()}
    s0.check()

    s1 = BatchScheduler(spec_engine)
    for r in reqs():
        s1.submit(r)
    out = {rid: list(r.generated) for rid, r in s1.run().items()}
    s1.check()
    assert s1.pool.all_free(), "draft/target pages leaked after the run"
    assert out == ref
    m = s1.metrics
    # every spec-engine segment is one draft/verify round, and K drafts
    # are proposed per resident spec row per round
    assert m["spec_rounds"] == m["segments"] > 0
    assert m["draft_proposed"] > 0
    assert 0 <= m["draft_accepted"] <= m["draft_proposed"]


# ---------------------------------------------------------------------------
# accept_speculative math
# ---------------------------------------------------------------------------

def test_greedy_accept_longest_prefix_and_carry():
    v, k = 8, 3
    tgt = jnp.array([[1, 2, 3, 4]])               # argmax chain o_0..o_3
    target_logits = jax.nn.one_hot(tgt, v) * 5.0  # [1, K+1, V]
    for match in range(k + 1):
        drafts = jnp.array([[1, 2, 3][:match] + [7] * (k - match)],
                           jnp.int32)
        acc, carry = accept_speculative(
            drafts, jnp.zeros((1, k, v)), target_logits, policy="greedy")
        assert int(acc[0]) == match
        # carry is o_a verbatim: next argmax continues the target chain
        assert int(jnp.argmax(carry[0])) == int(tgt[0, match])


def test_accept_spec_mask_false_forces_plain_target():
    v, k, t = 8, 2, 0.7
    key = jax.random.PRNGKey(0)
    kq, ko, ka = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, k, v))
    o = jax.random.normal(ko, (1, k + 1, v))
    acc, carry = accept_speculative(
        jnp.zeros((1, k), jnp.int32), q, o, ka, policy="rejection",
        temperature=t, spec_mask=jnp.array([False]))
    assert int(acc[0]) == 0
    # carry distribution == plain p_0, not the residual
    np.testing.assert_allclose(
        np.asarray(jax.nn.softmax(carry[0] / t)),
        np.asarray(jax.nn.softmax(o[0, 0] / t)), rtol=1e-5, atol=1e-6)


def test_rejection_first_token_matches_target_distribution():
    v, t, n = 16, 0.8, 4096
    kq, ko = jax.random.split(jax.random.PRNGKey(3))
    q_logits = jax.random.normal(kq, (1, 1, v))
    o_logits = jax.random.normal(ko, (1, 2, v))

    def trial(key):
        kd, ka, kc = jax.random.split(key, 3)
        d = jax.random.categorical(kd, q_logits[:, 0] / t)     # draft ~ q
        acc, carry = accept_speculative(
            d[:, None].astype(jnp.int32), q_logits, o_logits, ka,
            policy="rejection", temperature=t)
        alt = jax.random.categorical(kc, carry[0] / t)  # residual draw
        return jnp.where(acc[0] == 1, d[0], alt)

    toks = jax.vmap(trial)(jax.random.split(jax.random.PRNGKey(17), n))
    hist = np.bincount(np.asarray(toks), minlength=v) / n
    want = np.asarray(jax.nn.softmax(o_logits[0, 0] / t))
    assert np.abs(hist - want).sum() < 0.12, (hist, want)


def test_rejection_engine_smoke(models):
    lm, tp, dp = models
    scfg = dataclasses.replace(SCFG, temperature=0.7)
    spec = SpecConfig(draft_config=DCFG, num_draft_tokens=3)
    eng = Engine(lm, tp, scfg, spec=spec, draft_params=dp)
    out = eng.generate(PROMPTS, max_new_tokens=12)
    assert [len(t) for t in out] == [12, 12, 12]
    assert all(0 <= tok < TCFG.vocab for t in out for tok in t)
    assert eng.spec_stats["proposed"] > 0


# ---------------------------------------------------------------------------
# chaos + snapshots on spec batches
# ---------------------------------------------------------------------------

def test_chaos_cancel_expire_leaves_closure(spec_engine):
    from repro.ft.chaos import ChaosEvent, ChaosSchedule
    chaos = ChaosSchedule(events=[
        ChaosEvent(segment=1, kind="cancel_request"),
        ChaosEvent(segment=2, kind="expire_request", device=1),
    ])
    sched = BatchScheduler(spec_engine, chaos=chaos)
    reqs = [Request(rid=i, prompt=[3 + i, 7, 11], max_new_tokens=20,
                    spec=(i % 2 == 0)) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    sched.run()
    sched.check()
    assert sched.pool.all_free(), "faulted spec rows leaked pages"
    assert all(sched.requests[r.rid].terminal for r in reqs)
    assert all(e.applied for e in chaos.events)
    kinds = {e["kind"] for e in sched.ft_events if e["type"] == "chaos"}
    assert {"cancel_request", "expire_request"} <= kinds
    # no token past the fault flag for the cancelled/expired rows
    aborted = [r for r in reqs if sched.requests[r.rid].rid
               in sched.aborted]
    assert aborted, "chaos never removed a request"


def test_restore_rejects_spec_signature_mismatch(models, spec_engine,
                                                 tmp_path):
    from repro.checkpoint import store
    lm, tp, dp = models
    sched = BatchScheduler(spec_engine, snapshot_dir=str(tmp_path),
                           snapshot_every=1)
    for i in range(2):
        sched.submit(Request(rid=i, prompt=[2 + i, 3, 4],
                             max_new_tokens=16, spec=True))
    sched.run(max_segments=2)
    snap = store.latest_snapshot(str(tmp_path))
    assert snap is not None
    other = Engine(lm, tp, SCFG,
                   spec=SpecConfig(draft_config=DCFG, num_draft_tokens=3),
                   draft_params=dp)
    with pytest.raises(ValueError, match="draft pairing"):
        other.restore(snap)
    # a PLAIN engine must refuse a spec snapshot too
    plain = Engine(lm, tp, SCFG)
    with pytest.raises(ValueError, match="draft pairing"):
        plain.restore(snap)


def test_restore_same_pairing_reproduces_tokens(spec_engine, tmp_path):
    reqs = lambda: [Request(rid=i, prompt=[5 + i, 9, 2],  # noqa: E731
                            max_new_tokens=14, spec=True)
                    for i in range(3)]
    s0 = BatchScheduler(spec_engine)
    for r in reqs():
        s0.submit(r)
    want = {rid: list(r.generated) for rid, r in s0.run().items()}

    from repro.checkpoint import store
    s1 = BatchScheduler(spec_engine, snapshot_dir=str(tmp_path),
                        snapshot_every=1)
    for r in reqs():
        s1.submit(r)
    s1.run(max_segments=1)                     # "crash" after one segment
    s2 = spec_engine.restore(store.latest_snapshot(str(tmp_path)))
    s2.run()
    got = {rid: list(r.generated) for rid, r in s2.completed.items()}
    assert got == want


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_spec_config_validation_errors():
    good = SpecConfig(draft_config=DCFG, num_draft_tokens=4)
    good.validate(TCFG, SCFG)                  # sanity: the pairing is ok
    with pytest.raises(ValueError, match=">= 1"):
        SpecConfig(draft_config=DCFG, num_draft_tokens=0).validate(TCFG)
    with pytest.raises(ValueError, match="accept_policy"):
        SpecConfig(draft_config=DCFG, accept_policy="maybe").validate(TCFG)
    with pytest.raises(ValueError, match="vocab mismatch"):
        SpecConfig(draft_config=dataclasses.replace(
            DCFG, vocab=512)).validate(TCFG)
    with pytest.raises(ValueError, match="paged engine"):
        good.validate(TCFG, dataclasses.replace(SCFG, page_size=0))
    with pytest.raises(ValueError, match="temperature 0"):
        SpecConfig(draft_config=DCFG, accept_policy="greedy").validate(
            TCFG, dataclasses.replace(SCFG, temperature=0.5))
    with pytest.raises(ValueError, match="temperature > 0"):
        SpecConfig(draft_config=DCFG, accept_policy="rejection").validate(
            TCFG, SCFG)
    with pytest.raises(ValueError, match="temperature-only"):
        good.validate(TCFG, dataclasses.replace(SCFG, temperature=0.5,
                                                top_k=5))


def test_cli_spec_kwargs_validation():
    from repro.launch import cli

    def ns(**kw):
        base = dict(draft=None, spec_tokens=4, accept_policy="auto",
                    smoke_dims=True)
        base.update(kw)
        return argparse.Namespace(**base)

    assert cli.spec_kwargs(ns(), TCFG, SCFG) == {}
    with pytest.raises(ValueError, match="need --draft"):
        cli.spec_kwargs(ns(spec_tokens=6), TCFG, SCFG)
    with pytest.raises(ValueError, match="beam"):
        cli.spec_kwargs(ns(draft="qwen2-0.5b", beam_width=2), TCFG, SCFG)
    with pytest.raises(ValueError, match="vocab mismatch"):
        cli.spec_kwargs(ns(draft="qwen2-0.5b", smoke_dims=False),
                        TCFG, SCFG)
    with pytest.raises(ValueError, match="encoder-decoder"):
        # match the encdec smoke config's vocab so the family check is
        # what trips, not the vocab one
        cli.spec_kwargs(ns(draft="seamless-m4t-medium"),
                        dataclasses.replace(TCFG, vocab=512), SCFG)
    kw = cli.spec_kwargs(ns(draft="qwen2-0.5b"), TCFG, SCFG)
    assert kw["spec"].draft_config.vocab == TCFG.vocab


def test_engine_rejects_spec_without_draft_params(models):
    lm, tp, _dp = models
    with pytest.raises(ValueError, match="draft_params"):
        Engine(lm, tp, SCFG,
               spec=SpecConfig(draft_config=DCFG, num_draft_tokens=4))
