"""The benchmark's traffic generator: seeded, on the menu, and the prefill
shapes it can reach are the ones set-up warms."""

import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import traffic  # noqa: E402
from repro.serve.kv_pool import KVPool  # noqa: E402

MIXES = os.path.join(ROOT, "chipbench", "traffic")
VOCAB = 151936


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,rate", [("chat", 40.0), ("docqa", 8.0)])
def test_same_seed_same_schedule(name, rate):
    a = traffic.schedule(mix(name), rate, 10, 2**33 + 7, VOCAB)
    b = traffic.schedule(mix(name), rate, 10, 2**33 + 7, VOCAB)
    assert [(x.due_s, x.prompt, x.budget) for x in a] == \
        [(x.due_s, x.prompt, x.budget) for x in b]
    c = traffic.schedule(mix(name), rate, 10, 2**33 + 8, VOCAB)
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_seeds_offer_the_same_sizes_in_another_order():
    a = traffic.schedule(mix("chat"), 40.0, 10, 11, VOCAB)
    b = traffic.schedule(mix("chat"), 40.0, 10, 12, VOCAB)
    assert len(a) == len(b) == 400
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.budget for x in a) == sorted(x.budget for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert 0 <= min(x.due_s for x in a) and max(x.due_s for x in a) < 10


def test_arrivals_are_poisson():
    """Gaps are exponential, so bursts of short gaps come as in a Poisson
    stream: mean 1 / rate, coefficient of variation 1, about 1 gap in 10
    under a tenth of the mean, and runs of three gaps each under a
    quarter of it about once in 90."""
    a = traffic.schedule(mix("chat"), 40.0, 100, 2**33 + 1, VOCAB)
    gaps = np.diff([x.due_s for x in a])
    mean = gaps.mean()
    assert mean == pytest.approx(1 / 40, rel=0.03)
    assert gaps.std() / mean == pytest.approx(1.0, abs=0.06)
    assert np.mean(gaps < 0.1 * mean) == pytest.approx(1 - np.exp(-0.1),
                                                        abs=0.02)
    short = gaps < 0.25 * mean
    bursts = np.sum(short[:-2] & short[1:-1] & short[2:])
    assert 20 <= bursts <= 75          # (1 - e^-0.25)^3 * 4000 = 43


@pytest.mark.parametrize("name,rate", [("chat", 4.0), ("docqa", 2.0)])
def test_lead_in_is_offered_and_not_measured(name, rate):
    """A lead-in of rate * lead sessions comes before the window opens;
    the window's own sessions and their sizes do not depend on it."""
    m = mix(name)
    seed = 2**32 + 3
    with_lead = traffic.schedule(m, rate, 20, seed, VOCAB, lead_s=10)
    alone = traffic.schedule(m, rate, 20, seed, VOCAB)
    lead = [a for a in with_lead if not a.measured]
    win = [a for a in with_lead if a.measured]
    assert all(-10 <= a.due_s < 0 for a in lead if a.prefix_len == 0)
    assert len({a.session for a in lead}) == round(rate * 10)
    assert all(0 <= a.due_s < 20 for a in win)
    assert len({a.session for a in win}) == round(rate * 20)
    if name == "chat":          # one turn a session: all are offered
        assert sorted(a.budget for a in win) == \
            sorted(a.budget for a in alone)
    assert all(a.measured for a in alone)
    assert [a.rid for a in with_lead] == list(range(len(with_lead)))


@pytest.mark.parametrize("name,rate", [("chat", 40.0), ("docqa", 8.0)])
def test_every_length_is_on_the_menu(name, rate):
    m = mix(name)
    menu = set(traffic.menu(m))
    sched = traffic.schedule(m, rate, 20, 5, VOCAB)
    assert sched and all(len(a.prompt) in menu for a in sched)
    plain = set(traffic.prefill_shapes(m)["plain"])
    assert all(len(a.prompt) in plain for a in sched)
    assert all(0 <= a.due_s < 20 for a in sched)
    assert [a.rid for a in sched] == list(range(len(sched)))


def test_chat_shape():
    sched = traffic.schedule(mix("chat"), 40.0, 30, 3, VOCAB)
    lens = np.array([len(a.prompt) for a in sched])
    outs = np.array([a.budget for a in sched])
    assert lens.min() >= 64 and lens.max() <= 1408
    assert 500 <= np.median(lens) <= 600          # median 512, snapped up
    assert outs.min() >= 16 and outs.max() <= 512
    assert 150 <= np.median(outs) <= 170
    assert all(a.prefix_len == 0 for a in sched)
    firsts = [a.prompt[0] for a in sched]
    assert len(set(firsts)) == len(firsts)


def test_docqa_shape():
    m = mix("docqa")
    sched = traffic.schedule(m, 8.0, 30, 3, VOCAB)
    by = collections.defaultdict(list)
    for a in sched:
        by[a.session].append(a)
    # sessions that start with 20 s of the window left offer every turn
    full = [turns for turns in by.values() if turns[0].due_s < 10]
    assert all(3 <= len(t) <= 5 for t in full)
    for turns in by.values():
        assert turns[0].prefix_len == 0
        for t in turns[1:]:
            assert t.prompt[:t.prefix_len] == turns[0].prompt[:t.prefix_len]
            assert 504 <= t.prefix_len <= 1528
            assert (t.prefix_len + 8) % 64 == 0
            assert len(t.prompt) - t.prefix_len in (72, 136, 200, 264)
    assert all(32 <= a.budget <= 128 for a in sched)


def _replay(m, sched, page_size=16, slots=128, width=129):
    """Admit the schedule's prompts one at a time through the program's
    own KVPool (prefix cache on, qwen2-0.5b's pool of every slot at
    max_seq), as the scheduler does, and return the (plain, suffix)
    prefill lengths it produces.  Nothing is evicted at this load; an
    evicted page of a live document would leave a suffix off the menu,
    which the window would count among its compiles."""
    pool = KVPool(slots * width + 1, page_size, slots, width)
    plain, suffix = set(), set()
    for a in sched:
        adm = pool.admit_prefix(0, a.prompt)
        pool.reserve(0, len(a.prompt) + 8)
        pool.alloc(0, len(a.prompt))
        pool.register_prefix(0, a.prompt)
        n = len(a.prompt) - adm.matched_len
        (suffix if adm.matched_len else plain).add(n)
        if adm.matched_len:
            assert adm.cow is not None      # every hit forks a page
        pool.release(0)
    return plain, suffix


@pytest.mark.parametrize("seed", [1, 2**32 + 5])
def test_docqa_suffix_lengths_are_the_warmed_ones(seed):
    m = mix("docqa")
    sched = traffic.schedule(m, 4.0, 30, seed, VOCAB)
    plain, suffix = _replay(m, sched)
    shapes = traffic.prefill_shapes(m)
    assert suffix and suffix <= set(shapes["suffix"])
    assert plain <= set(shapes["plain"])
    assert set(shapes["suffix"]) == {72, 136, 200, 264}


def test_chat_never_hits_the_prefix_cache():
    m = mix("chat")
    plain, suffix = _replay(m, traffic.schedule(m, 40.0, 10, 9, VOCAB))
    assert not suffix
    assert plain <= set(traffic.prefill_shapes(m)["plain"])


def test_max_context_fits_max_seq():
    for name in ("chat", "docqa"):
        assert traffic.max_context(mix(name)) + 8 <= 2048


def test_stratified_order_spreads_each_band():
    rng = np.random.default_rng(4)
    v = np.arange(64.0)
    out = traffic.stratified(v, rng)
    assert sorted(out) == list(v)
    bands = out // 8                       # eight bands of eight values
    for k in range(8):
        assert sorted(bands[8 * k:8 * k + 8]) == list(range(8))
    again = traffic.stratified(v, np.random.default_rng(5))
    assert list(out) != list(again)
