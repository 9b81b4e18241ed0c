"""Request-plane robustness: lifecycle, bounded admission, snapshots, chaos.

Covers the PR's acceptance bars end to end on a tiny paged fp32 engine
(greedy, so every parity assertion is bit-exact):

* deadline / ttft-deadline expiry and host-side cancellation retire rows
  at segment boundaries with pages freed and no tokens returned past the
  flag;
* the bounded admission queue rejects overload in O(1) with a structured
  retryable error, sheds strictly-lower-priority work under
  ``shed-lowest``, and the bounded-bypass rule prevents the head-of-line
  starvation the old deque allowed (regression test);
* crash-safe snapshots round-trip atomically with CRC validation
  (corruption raises, never restores), and a killed run restored on a
  FRESH engine produces bit-identical greedy tokens;
* randomized churn with interleaved cancels/expiries/sheds keeps the
  full pool + scheduler invariant closure green at every step;
* corrupt persisted tune-table entries quarantine to ``*.corrupt`` and
  re-sweep instead of crashing dispatch.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import default_features
from repro.models.lm import LM, LMConfig
from repro.serve import (AdmissionQueue, AdmissionRejected, BatchScheduler,
                         Engine, KVPool, Request, ServeConfig)

CFG = LMConfig(name="robust-t", family="dense", vocab=64, d_model=32,
               n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)


@pytest.fixture(scope="module")
def lm_params():
    lm = LM(CFG, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(lm_params):
    """One shared PAGED engine: traced programs amortize across tests."""
    lm, params = lm_params
    return Engine(lm, params, ServeConfig(
        max_seq=128, batch_slots=4, temperature=0.0, eos_token=-1,
        admission_chunk=8, page_size=16))


def _reqs(n, plen=8, max_new=10, base=0, **kw):
    rng = np.random.default_rng(11 + base)
    return [Request(rid=base + i,
                    prompt=rng.integers(1, CFG.vocab, plen).tolist(),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _run_all(engine, reqs, **kw):
    sched = BatchScheduler(engine, **kw)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched


# ---------------------------------------------------------------------------
# AdmissionQueue unit behavior
# ---------------------------------------------------------------------------

def test_queue_priority_fifo_order():
    q = AdmissionQueue()
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=1, priority=p)
            for i, p in enumerate([2, 0, 1, 0, 2])]
    for r in reqs:
        q.push(r)
    assert [r.rid for r in q.ordered()] == [1, 3, 2, 0, 4]
    assert q.head().rid == 1


def test_queue_reject_new_is_retryable_and_o1():
    q = AdmissionQueue(max_queue=2)
    for r in _reqs(2):
        q.push(r)
    with pytest.raises(AdmissionRejected) as ei:
        q.push(_reqs(1, base=50)[0])
    rej = ei.value.rejection
    assert rej.reason == "queue_full" and rej.retryable
    assert rej.retry_after_s > 0 and rej.queue_depth == 2


def test_queue_shed_lowest_evicts_strictly_worse_only():
    q = AdmissionQueue(max_queue=2, shed_policy="shed-lowest")
    a, b = _reqs(2, base=0)
    a.priority, b.priority = 2, 2
    q.push(a)
    q.push(b)
    urgent = _reqs(1, base=10)[0]
    urgent.priority = 0
    victim = q.push(urgent)
    assert victim is b            # newest of the worst class
    assert len(q) == 2
    # an arrival no more urgent than the worst resident class is refused
    same = _reqs(1, base=20)[0]
    same.priority = 2
    with pytest.raises(AdmissionRejected):
        q.push(same)


def test_queue_close_refuses_nonretryable():
    q = AdmissionQueue()
    q.close()
    with pytest.raises(AdmissionRejected) as ei:
        q.push(_reqs(1)[0])
    assert ei.value.rejection.reason == "draining"
    assert not ei.value.rejection.retryable


# ---------------------------------------------------------------------------
# KVPool seize / snapshot index plumbing
# ---------------------------------------------------------------------------

def test_pool_seize_shrinks_and_check_passes():
    pool = KVPool(16, 4, 2, 8)
    free0 = len(pool.free)
    got = pool.seize(5)
    assert got == 5 and len(pool.free) == free0 - 5
    pool.check()
    assert pool.unseize() == 5 and len(pool.free) == free0
    pool.check()


def test_pool_export_adopt_index_roundtrip():
    pool = KVPool(32, 4, 2, 8, prefix_cache=True)
    toks = list(range(1, 13))                  # 3 full pages of 4
    pool.reserve(0, 16)
    pool.alloc(0, len(toks))
    pool.register_prefix(0, toks)
    nodes = pool.export_index()
    assert len(nodes) == 3
    pool2 = KVPool(32, 4, 2, 8, prefix_cache=True)
    assert pool2.adopt_index(nodes) == 3
    pool2.check()
    # matchable span excludes the final token (prefill needs >= 1 real
    # token): 11 usable = 2 full pages + a 3-token in-page partial
    matched, shared = pool2.match_prefix(toks)
    assert matched == 11 and shared == 2


# ---------------------------------------------------------------------------
# snapshot format: atomic, versioned, CRC-validated
# ---------------------------------------------------------------------------

def test_snapshot_roundtrip_and_corruption(tmp_path):
    from repro.checkpoint import store
    payload = {"a": 1, "arr": np.arange(6, dtype=np.float32).reshape(2, 3),
               "nested": [{"b": np.int64(7)}]}
    p = str(tmp_path / "s.snap")
    store.save_serving_snapshot(p, payload)
    back = store.load_serving_snapshot(p)
    assert back["a"] == 1 and back["nested"][0]["b"] == 7
    np.testing.assert_array_equal(back["arr"], payload["arr"])
    # flip one payload byte -> CRC refuses
    blob = bytearray(open(p, "rb").read())
    blob[-3] ^= 0x01
    open(p, "wb").write(bytes(blob))
    with pytest.raises(store.SnapshotCorrupt):
        store.load_serving_snapshot(p)
    # truncation refuses too
    open(p, "wb").write(bytes(blob[: len(blob) // 2]))
    with pytest.raises(store.SnapshotCorrupt):
        store.load_serving_snapshot(p)
    with pytest.raises(FileNotFoundError):
        store.load_serving_snapshot(str(tmp_path / "missing.snap"))


def test_snapshot_retention(engine, tmp_path):
    sched = BatchScheduler(engine, snapshot_dir=str(tmp_path),
                           snapshot_every=1, snapshot_keep=2)
    for r in _reqs(6, base=900, max_new=12):
        sched.submit(r)
    sched.run()
    from repro.checkpoint import store
    snaps = store.list_snapshots(str(tmp_path))
    assert 0 < len(snaps) <= 2
    assert sched.metrics["snapshots"] >= 3


# ---------------------------------------------------------------------------
# lifecycle: deadlines, cancellation, shed — no token past the flag
# ---------------------------------------------------------------------------

def test_deadline_expiry_frees_slot_and_pages(engine):
    reqs = _reqs(4, base=100, max_new=24)
    reqs[1].deadline_ms = 0.0          # expired by the first boundary
    sched = _run_all(engine, reqs)
    assert 101 not in sched.completed
    assert sched.aborted[101].status == "expired"
    assert sched.metrics["expired"] == 1
    assert any(e["type"] == "expiry" and e["rid"] == 101
               for e in sched.ft_events)
    assert len(sched.completed) == 3
    sched.check()                       # pool leak would trip here


def test_ttft_deadline_only_gates_first_token(engine):
    reqs = _reqs(2, base=120, max_new=8)
    # generous ttft deadline: must NOT expire (first token lands fast)
    reqs[0].ttft_deadline_ms = 60_000.0
    sched = _run_all(engine, reqs)
    assert len(sched.completed) == 2


def test_cancel_queued_and_active(engine):
    reqs = _reqs(6, base=140, max_new=24)
    sched = BatchScheduler(engine)
    for r in reqs:
        sched.submit(r)
    assert sched.cancel(145)           # still queued: dequeued on sweep
    reqs[0].cancel()                   # request-side token, active row
    sched.run()
    for rid in (140, 145):
        assert rid not in sched.completed
        assert sched.aborted[rid].status == "cancelled"
    # no token was returned after the flag was observable
    assert sched.aborted[140].generated == []
    assert sched.aborted[145].generated == []
    assert not sched.cancel(141)       # terminal: no-op
    assert not sched.cancel(99999)     # unknown: no-op
    assert len(sched.completed) == 4


def test_shed_lowest_under_pressure(engine):
    sched = BatchScheduler(engine, max_queue=2, shed_policy="shed-lowest")
    batchy = _reqs(2, base=160, priority=2)
    for r in batchy:
        sched.submit(r)
    urgent = _reqs(1, base=170, priority=0)[0]
    sched.submit(urgent)
    assert sched.metrics["sheds"] == 1
    shed = [r for r in batchy if r.status == "shed"]
    assert len(shed) == 1 and shed[0].rid in sched.aborted
    sched.run()
    assert urgent.rid in sched.completed
    assert shed[0].rid not in sched.completed


def test_rejection_records_event(engine):
    sched = BatchScheduler(engine, max_queue=1)
    sched.submit(_reqs(1, base=180)[0])
    with pytest.raises(AdmissionRejected):
        sched.submit(_reqs(1, base=190)[0])
    assert sched.metrics["rejections"] == 1
    assert any(e["type"] == "reject" for e in sched.ft_events)
    sched.run()


def test_drain_finishes_accepted_work(engine):
    sched = BatchScheduler(engine)
    for r in _reqs(3, base=200):
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 3
    with pytest.raises(AdmissionRejected) as ei:
        sched.submit(_reqs(1, base=210)[0])
    assert ei.value.rejection.reason == "draining"


# ---------------------------------------------------------------------------
# bounded bypass: the starvation regression test
# ---------------------------------------------------------------------------

def test_bounded_bypass_prevents_head_starvation(lm_params):
    """A large head request must not be starved by an endless stream of
    small later arrivals: after ``max_bypass`` bypasses the queue blocks
    until pages drain to the head.  (The old unbounded-deque scheduler
    admitted smalls forever.)"""
    lm, params = lm_params
    # pool sized so the big request CANNOT fit while >=2 smalls run, but
    # fits alone: pages are the contended resource
    eng = Engine(lm, params, ServeConfig(
        max_seq=128, batch_slots=4, temperature=0.0, admission_chunk=4,
        page_size=16, pool_pages=17))    # 16 usable pages + null
    K = 2
    sched = BatchScheduler(eng, max_bypass=K)
    big = Request(rid=1000, prompt=list(range(1, 65)),    # 64 tokens
                  max_new_tokens=32)                      # worst 7 pages
    sched.submit(big)
    smalls = _reqs(10, base=2000, plen=16, max_new=16)    # worst 3 pages
    for r in smalls:
        sched.submit(r)
    sched.run()
    assert 1000 in sched.completed and len(sched.completed) == 11
    order = [rid for rid, _slot in sched.admission_log]
    big_pos = order.index(1000)
    # the head was bypassed at most K times before admission blocked
    assert big_pos <= K, \
        f"big request starved: admitted {big_pos} smalls first (> {K})"
    assert sched.metrics["bypasses"] <= K


# ---------------------------------------------------------------------------
# kill-and-restore parity (the acceptance bar)
# ---------------------------------------------------------------------------

def test_kill_and_restore_token_parity(engine, lm_params, tmp_path):
    base = _run_all(engine, _reqs(6, base=300, max_new=12))
    want = {rid: list(r.generated) for rid, r in base.completed.items()}

    sched = BatchScheduler(engine, snapshot_dir=str(tmp_path),
                           snapshot_every=1)
    for r in _reqs(6, base=300, max_new=12):
        sched.submit(r)
    sched.run(max_segments=1)          # killed mid-flight
    assert len(sched.completed) < 6
    from repro.checkpoint import store
    snap = store.latest_snapshot(str(tmp_path))
    # restore on a FRESH engine (new traced programs, new pool)
    lm, params = lm_params
    eng2 = Engine(lm, params, engine.cfg)
    sched2 = eng2.restore(snap)
    assert sched2.metrics["restores"] == 1
    sched2.run()
    got = {rid: list(r.generated) for rid, r in sched2.completed.items()}
    assert got == want, "restored tokens diverged from uninterrupted run"


def test_restore_rejects_config_mismatch(engine, lm_params, tmp_path):
    sched = BatchScheduler(engine, snapshot_dir=str(tmp_path))
    for r in _reqs(2, base=350):
        sched.submit(r)
    sched.run()
    from repro.checkpoint import store
    snap = store.latest_snapshot(str(tmp_path))
    lm, params = lm_params
    other = Engine(lm, params, ServeConfig(
        max_seq=64, batch_slots=4, temperature=0.0, page_size=16))
    with pytest.raises(ValueError, match="config mismatch"):
        other.restore(snap)


# ---------------------------------------------------------------------------
# randomized churn: invariants green under interleaved faults
# ---------------------------------------------------------------------------

class _ChurnHook:
    """Duck-typed chaos hook: randomized cancels + invariant closure at
    EVERY segment boundary, and a record of each aborted request's token
    count at abort time (nothing may be appended after)."""

    def __init__(self, sched_reqs, seed=3):
        self.rng = np.random.default_rng(seed)
        self.reqs = sched_reqs
        self.aborted_len = {}

    def tick(self, sched, segment):
        live = [r for r in self.reqs
                if not r.terminal and self.rng.random() < 0.2]
        for r in live[:1]:
            sched.cancel(r.rid)
        for r in self.reqs:
            if r.terminal and r.status in ("cancelled", "expired"):
                n = self.aborted_len.setdefault(r.rid, len(r.generated))
                assert len(r.generated) == n, \
                    f"request {r.rid} gained tokens after {r.status}"
        sched.check()


def test_randomized_churn_invariants(engine):
    reqs = _reqs(14, base=400, max_new=20,)
    for i, r in enumerate(reqs):
        r.priority = i % 3
        if i % 5 == 4:
            r.deadline_ms = 30.0       # some expire mid-run
    hook = _ChurnHook(reqs)
    sched = BatchScheduler(engine, max_queue=8, shed_policy="shed-lowest",
                           chaos=hook)
    shed_rejected = 0
    for r in reqs:
        try:
            sched.submit(r)
        except AdmissionRejected:
            shed_rejected += 1
    sched.run()
    sched.check()
    # every submitted request reached a terminal state — no hang, no limbo
    for r in reqs:
        assert r.terminal, f"request {r.rid} ended non-terminal: {r.status}"
    # token budgets were never exceeded, aborted rows gained nothing after
    for r in reqs:
        assert len(r.generated) <= r.max_new_tokens
    done = set(sched.completed)
    dead = set(sched.aborted)
    assert done | dead | {r.rid for r in reqs if r.status == "rejected"} \
        == {r.rid for r in reqs}


# ---------------------------------------------------------------------------
# quarantine: corrupt tune-table entries re-sweep instead of crashing
# ---------------------------------------------------------------------------

def test_artifact_cache_quarantines_corrupt_entry(tmp_path):
    from repro.core.artifact_cache import ArtifactCache
    cache = ArtifactCache(str(tmp_path))
    cache.put("ab" * 32, {"kind": "x", "choice": [1, 2]})
    path = cache._entry_path("ab" * 32)
    open(path, "w").write("{ not json")
    assert cache.get("ab" * 32) is None
    assert cache.stats.quarantined == 1
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)
    # a rewrite heals the entry; the quarantined bytes stay for forensics
    cache.put("ab" * 32, {"kind": "x", "choice": [3]})
    assert cache.get("ab" * 32)["choice"] == [3]
    assert os.path.exists(path + ".corrupt")


def test_registry_quarantines_garbage_tune_entry(tmp_path, monkeypatch):
    from repro.core.artifact_cache import ArtifactCache
    from repro.kernels import registry
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    cache = ArtifactCache(str(tmp_path))
    digest = registry._tune_digest("tune-choice", "attention", "bogus-key")
    # schema-valid JSON, garbage content: "choice" present but unusable
    cache.put(digest, {"kind": "tune-choice", "choice": 17,
                       "score_s": "not-a-number"})
    registry._TABLE.clear()
    with pytest.warns(RuntimeWarning, match="quarantined"):
        got = registry._best_from_disk("attention", "bogus-key")
    assert got is None                              # read as a miss
    assert os.path.exists(cache._entry_path(digest) + ".corrupt")
    # warn-once: the second lookup is silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert registry._best_from_disk("attention", "bogus-key") is None


# ---------------------------------------------------------------------------
# chaos schedule determinism + CLI plumbing
# ---------------------------------------------------------------------------

def test_chaos_schedule_seed_determinism():
    from repro.ft.chaos import ChaosSchedule
    a = ChaosSchedule(seed=42)
    b = ChaosSchedule(seed=42)
    assert [(e.segment, e.kind, e.magnitude) for e in a.events] \
        == [(e.segment, e.kind, e.magnitude) for e in b.events]
    c = ChaosSchedule(seed=43)
    assert [(e.segment, e.kind) for e in a.events] \
        != [(e.segment, e.kind) for e in c.events]


def test_chaos_smoke_schedule_on_engine(engine, tmp_path):
    from repro.ft.chaos import ChaosSchedule
    chaos = ChaosSchedule.smoke()
    sched = BatchScheduler(engine, chaos=chaos,
                           snapshot_dir=str(tmp_path), snapshot_every=2)
    for r in _reqs(10, base=600, max_new=24):
        sched.submit(r)
    done = sched.run()
    assert len(done) == 10
    assert chaos.checks > 0
    kinds = {e["kind"] for e in sched.ft_events if e["type"] == "chaos"}
    assert "pool_exhaust" in kinds and "slow_segment" in kinds
    # single-device engine: death/flap are skip-noted, never crash
    assert all(k in ("heartbeat_flap", "device_death", "snapshot_corrupt")
               for k in chaos.summary()["skipped"])


def test_cli_ft_and_robustness_flags(tmp_path):
    import argparse
    from repro.launch import cli
    ap = argparse.ArgumentParser()
    cli.add_ft_args(ap)
    cli.add_robustness_args(ap)
    args = ap.parse_args([
        "--ft-timeout-steps", "5", "--ft-confirm", "3",
        "--straggler-threshold", "6.5", "--max-queue", "7",
        "--shed-policy", "shed-lowest", "--snapshot-dir", str(tmp_path),
        "--snapshot-every", "4", "--chaos", "9"])
    ft = cli.ft_kwargs(args)
    assert ft["ft_timeout_steps"] == 5 and ft["ft_confirm"] == 3
    assert ft["straggler_threshold"] == 6.5
    rb = cli.robustness_kwargs(args)
    assert rb["max_queue"] == 7 and rb["shed_policy"] == "shed-lowest"
    assert rb["snapshot_every"] == 4
    assert rb["chaos"].seed == 9
    # eager validation: --snapshot-every without --snapshot-dir
    args2 = ap.parse_args(["--snapshot-every", "2"])
    with pytest.raises(ValueError, match="snapshot-dir"):
        cli.robustness_kwargs(args2)


def test_serve_json_includes_robustness(tmp_path, monkeypatch):
    """launch/serve.py end-to-end with the new flags (tiny smoke)."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.serve import main
    # the launcher turns JAX's persistent compile cache on: keep it in tmp
    # and off again for the rest of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    was = jax.config.jax_compilation_cache_dir
    out = str(tmp_path / "serve.json")
    try:
        rc = main(["--arch", "qwen2-0.5b", "--smoke-dims", "--requests",
                   "4", "--prompt-len", "6", "--max-new", "4",
                   "--max-seq", "64", "--max-queue", "2", "--snapshot-dir",
                   str(tmp_path / "snaps"), "--json", out])
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    assert rc == 0
    d = json.load(open(out))
    assert d["rejections"] == 2 and d["snapshots"] >= 1
    assert any(e["type"] == "reject" for e in d["ft_events"])
