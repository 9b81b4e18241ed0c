"""The serving program's own measurement (serve/engine.py, models/):
host spans through ``Engine.span``, ``jax.named_scope`` on the decode and
prefill programs' parts, and the build counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import default_features
from repro.core.perfctr import PerfCtr
from repro.models.lm import LM, LMConfig
from repro.serve.engine import (DECODE_REGION, PREFILL_REGION, BatchScheduler,
                                Engine, Request, ServeConfig)

CFG = LMConfig(name="t", family="dense", vocab=64, d_model=32, n_layers=2,
               num_heads=4, num_kv_heads=2, d_ff=64)


@pytest.fixture(scope="module")
def lm_params():
    lm = LM(CFG, default_features().with_(remat_policy="none"))
    return lm, lm.init(jax.random.PRNGKey(0))


def paged(lm_params, **kw):
    lm, params = lm_params
    return Engine(lm, params, ServeConfig(max_seq=64, batch_slots=2,
                                          page_size=8, **kw))


def test_region_wall_time_is_taken_around_the_sync(lm_params):
    """Instrumented, each scheduler segment's fetch adds one wall time to
    serve.decode; prefills are dispatches and add none."""
    eng = paged(lm_params)
    ctr = PerfCtr()
    eng.instrument(ctr, prompt_len=4)
    sched = BatchScheduler(eng)
    for rid in range(3):
        sched.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                             max_new_tokens=3))
    sched.run()
    assert len(ctr.regions[DECODE_REGION].wall_times) == \
        sched.metrics["segments"]
    assert ctr.regions[PREFILL_REGION].wall_times == []


def test_span_without_a_perfctr_is_the_profiler_annotation(lm_params):
    from jax.profiler import TraceAnnotation
    eng = paged(lm_params)
    assert isinstance(eng.span("serve.fetch", DECODE_REGION, seg=3),
                      TraceAnnotation)
    with eng.span("serve.admit", rid=1) as sp:
        sp.set_metadata(prefix=0)


def _op_names(text):
    """The scope path of every instruction, as a tuple of its parts."""
    return {tuple(ln.split('op_name="', 1)[1].split('"', 1)[0].split("/"))
            for ln in text.splitlines() if 'op_name="' in ln}


def _under(names, *scopes):
    return any(all(s in n for s in scopes) for n in names)


def test_decode_segment_ops_carry_their_scopes(lm_params):
    eng = paged(lm_params)
    state = eng.lm.init_decode_state(2, 64, **eng._state_kwargs())
    logits = jnp.zeros((2, CFG.vocab), eng.lm.dtype)
    text = eng.decode_segment(2).lower(
        eng.params, state, logits, jax.random.key(0)).compile().as_text()
    names = _op_names(text)
    for part in ("kv_cache", "attention", "mlp"):
        assert _under(names, "jit(seg)", "layers", part), part
    assert _under(names, "jit(seg)", "head")
    assert not _under(names, "layers", "head")


def test_prefill_writes_pages_under_kv_cache(lm_params):
    eng = paged(lm_params)
    state = eng.lm.init_decode_state(2, 64, **eng._state_kwargs())
    logits = jnp.zeros((2, CFG.vocab), eng.lm.dtype)
    toks = jnp.ones((1, 12), jnp.int32)
    text = eng._paged_slot_prefill.lower(
        eng.params, state, logits, toks, jnp.asarray(0, jnp.int32),
        jnp.arange(eng.table_width, dtype=jnp.int32), None
    ).compile().as_text()
    names = _op_names(text)
    assert _under(names, "layers", "attention", "kv_cache", "scatter")
    assert _under(names, "head")


def test_builds_count_each_new_shape_key_once(lm_params):
    eng = paged(lm_params)
    state = eng.lm.init_decode_state(2, 64, **eng._state_kwargs())
    logits = jnp.zeros((2, CFG.vocab), eng.lm.dtype)
    table = np.arange(eng.table_width, dtype=np.int32)
    state = eng.set_page_table(state, np.stack([table, table]))
    assert eng.programs_built == 0
    state = eng.copy_pages(state, [(1, 2)])                     # (1,)
    state = eng.copy_pages(state, [(1, 2), (3, 4), (5, 6)])     # (4,)
    state = eng.copy_pages(state, [(1, 2)] * 4)                 # (4,) again
    assert eng.programs_built == 2
    for n in (5, 5, 7):
        state, logits = eng.prefill_slot(state, logits, [1] * n, 0,
                                         table_row=table)
    assert eng.programs_built == 4
    state, logits = eng.prefill_slot(state, logits, [1] * 5, 0,
                                     table_row=table, prefix_len=8)
    assert eng.programs_built == 5                  # a prefix is a new key
    seg = eng.decode_segment(2)
    state = eng._with_lengths(state, jnp.zeros(2, jnp.int32))
    _, logits, state, _ = seg(eng.params, state, logits, jax.random.key(0))
    narrow = eng.set_page_table(state, np.stack([table, table])[:, :4])
    seg(eng.params, narrow, logits, jax.random.key(0))
    assert eng.programs_built == 7                  # (2, width), (2, 4)
