"""The four-chip cell's serving programs compiled for a described TPU v5e
``2x2``, at its configuration's full size: one decode segment at the
widest page table and one slot prefill of the longest prompt of the chat
mix, on the configuration's (1, 4) (data, model) mesh.  Nothing runs; the
compiler says whether each program fits a chip's HBM, keeps its Pallas
kernel and exchanges the layers' partial sums across the chips.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import traffic  # noqa: E402
from chipbench.lookup import Lookup  # noqa: E402

#: a v5e chip's HBM as the compiler counts it
HBM = 15.75 * 2**30
CELL = "qwen2-vl-7b.tp4.chat"


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip executable can be written to the persistent
        # cache but never read back without the chip: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield Mesh(np.array(topo.devices).reshape(1, 4),
                       ("data", "model"))
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def served(mesh):
    """The cell's engine over abstract weights placed by the program's own
    rules, and its decode state, logits and key as the scheduler shards
    them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve import Engine
    lk = Lookup()
    cfg = lk.config(lk.workload(CELL)["config"])
    assert tuple(cfg["serve"]["mesh"]) == (1, 4)
    fam = lk.family(cfg["family"])
    with pytest.MonkeyPatch.context() as mp:
        # the registry decides from the backend: take its TPU branch, as on
        # the chip (this process's backend is the CPU)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        # nothing can be put on a described device: the weights stay
        # abstract, in the shardings the rules give them
        mp.setattr(Engine, "_shard_params", lambda self, p: p)
        lm = fam._lm(cfg)

        def put(tree, specs):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
                tree, specs)

        shapes = jax.eval_shape(lambda: lm.init_params(
            jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        params = put(shapes, lm.param_pspecs(mesh, shapes))
        eng = Engine(lm, params, fam.serve_config(cfg), mesh=mesh)
        state = jax.eval_shape(lambda: lm.init_decode_state(
            eng.cfg.batch_slots, eng.cfg.max_seq, **eng._state_kwargs()))
        state = put(state, jax.tree.map(eng._state_spec, state))
        rep = NamedSharding(mesh, P())
        logits = jax.ShapeDtypeStruct((eng.cfg.batch_slots, lm.cfg.vocab),
                                      jnp.bfloat16, sharding=rep)
        key = jax.eval_shape(lambda: jax.random.key(0))
        key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
        yield lk, eng, params, state, logits, key, rep


def _check(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM, mem
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce(" in text
    return used


def test_decode_segment_compiles_on_the_mesh(served):
    _lk, eng, params, state, logits, key, _rep = served
    c = state["caches"]
    seg = eng.decode_segment(eng.seg_cap)
    with eng._impl_ctx():
        compiled = seg.lower(params, state, logits, key).compile()
    assert c.page_table.shape[-1] == eng.table_width
    _check(compiled)


def test_longest_prefill_compiles_on_the_mesh(served):
    lk, eng, params, state, logits, _key, rep = served
    mix = lk.mix(lk.workload(CELL)["traffic"])
    longest = max(traffic.prefill_shapes(mix)["plain"])
    assert longest == 1408

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
    with eng._impl_ctx():
        compiled = eng._paged_slot_prefill.lower(
            params, state, logits, arg((1, longest)), arg(()),
            arg((eng.table_width,)), None).compile()
    _check(compiled)
