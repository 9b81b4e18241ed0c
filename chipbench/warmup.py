"""Set-up's warm-up: every program the window can run, built before the
window opens, through the same calls the scheduler makes.

The scheduler's programs are keyed by shape:

* slot prefill, one per prompt length, and one per suffix length after a
  prefix hit (the prefix program takes ``prefix_len`` as an argument);
* the copy-on-write page copy at a fork page (one pair per admission);
* decode segments, one per (steps in {1, 2, 4, 8}) x (page-table width,
  in buckets of 4 pages up to the table's width), with the eager
  page-table upload of that width;
* the eager operations of ``run()`` itself (a fresh decode state, the
  logits buffer, the PRNG key), warmed by one real request.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import BatchScheduler, Request
from repro.serve.kv_pool import pages_for


def steps_set(eng) -> List[int]:
    """The step counts a segment is quantized to: powers of two up to
    the admission chunk."""
    return [1 << i for i in range(eng.seg_cap.bit_length())]


def width_buckets(eng, max_context: int) -> List[int]:
    """Every table width a decode segment can be handed when no context
    exceeds ``max_context`` tokens: 4-page buckets from the smallest up,
    capped at the table's width."""
    top = pages_for(max_context + eng.seg_cap, eng.cfg.page_size)
    top = min(-(-top // 4) * 4, eng.table_width)
    out = list(range(4, top + 1, 4))
    if top not in out:
        out.append(top)
    return out


def warm(eng, shapes: Dict[str, List[int]], max_context: int,
         seed: int = 0) -> Dict[str, int]:
    """Run each program once.  Returns the count and the seconds by
    kind."""
    cfg, lm = eng.cfg, eng.lm
    nslots, vocab = cfg.batch_slots, lm.cfg.vocab
    rng_np = np.random.default_rng(seed)
    counts = collections.Counter()

    t = time.perf_counter()
    # the eager set-up of run(), and one real admission and segment
    prompt = rng_np.integers(1, vocab, size=min(shapes["plain"])).tolist()
    sched = BatchScheduler(eng)
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=1))
    sched.run()
    counts["run"] += 1
    counts["run_s"] = round(time.perf_counter() - t, 3)
    t = time.perf_counter()

    def fresh():
        """The state and logits as ``run()`` makes them (on a mesh the
        pool sharded, the logits replicated)."""
        return (eng.shard_state(eng.lm.init_decode_state(
            nslots, cfg.max_seq, **eng._state_kwargs())),
            eng.replicate(jnp.zeros((nslots, vocab), lm.dtype)))

    table = np.zeros((nslots, eng.table_width), np.int32)
    need = pages_for(cfg.max_seq, cfg.page_size)
    table[0, :need] = np.arange(1, need + 1)
    state, logits = fresh()
    state = eng.set_page_table(state, table)
    for n in shapes["plain"]:
        toks = rng_np.integers(1, vocab, size=n).tolist()
        if eng.mesh is None:
            state, logits = eng.prefill_slot(state, logits, toks, 0,
                                             table_row=table[0])
        else:
            # on a mesh a program is built anew for every sharding of its
            # inputs: a run's first admission meets a fresh state, a later
            # one what a program gave back (in the shardings the compiler
            # chose), with the page table just set or not
            del state, logits
            state, logits = fresh()
            for set_table in (True, False, True):
                if set_table:
                    state = eng.set_page_table(state, table)
                state, logits = eng.prefill_slot(state, logits, toks, 0,
                                                 table_row=table[0])
        counts["prefill"] += 1
    for n in shapes["suffix"]:
        state = eng.copy_pages(state, [(1, need + 1)])
        toks = rng_np.integers(1, vocab, size=n).tolist()
        state, logits = eng.prefill_slot(state, logits, toks, 0,
                                         table_row=table[0],
                                         prefix_len=cfg.page_size + 8)
        counts["prefill_suffix"] += 1
    if shapes["suffix"]:
        counts["cow_copy"] += 1
    jax.block_until_ready(logits)
    counts["prefill_s"] = round(time.perf_counter() - t, 3)
    t = time.perf_counter()

    rng = eng.replicate(jax.random.key(cfg.seed))
    for width in width_buckets(eng, max_context):
        for steps in steps_set(eng):
            # every row back to an empty context, so no write runs past
            # the narrow tables; then the table, as before every segment
            state = eng._with_lengths(
                state, eng.replicate(jnp.zeros(nslots, jnp.int32)))
            state = eng.set_page_table(state, table[:, :width])
            # dispatched without a wait: the next program traces while
            # this one runs
            toks, logits, state, rng = eng.decode_segment(steps)(
                eng.params, state, logits, rng)
            counts["decode"] += 1
    eng._fetch(toks)
    counts["decode_widths"] = len(width_buckets(eng, max_context))
    counts["decode_s"] = round(time.perf_counter() - t, 3)
    return dict(counts)
