"""Main-path Pallas kernels compiled for a described TPU v5e, at the
widths of ``configs/``.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, layouts Mosaic
cannot relayout, primitives with no Mosaic lowering, more VMEM than a
kernel may use.  Here each kernel is lowered with ``interpret=False`` and
compiled for one chip of a described ``v5e:2x2`` — no chip attached,
nothing runs — and the compiled text must hold the Mosaic custom call.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import registry

BF16 = jnp.bfloat16
QWEN = get_arch("qwen2-0.5b").config
QWEN_VL = get_arch("qwen2-vl-7b").config
ATTN_CFGS = [pytest.param(QWEN, id="qwen2-0.5b"),
             pytest.param(QWEN_VL, id="qwen2-vl-7b")]
#: one chip's share of a Qwen3-235B-A22B stage: 6 layers, experts 0-31
QWEN3_MOE = dataclasses.replace(get_arch("qwen3-moe-235b-a22b").config,
                                n_layers=6, moe_held_experts=32)
#: the grouped expert matmul's custom call, named after its jit
GMM = re.compile(r"%grouped_expert_matmul\.\d+ = .*custom-call\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
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
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cfg", ATTN_CFGS)
@pytest.mark.parametrize("seq", [77, 1024])
def test_pallas_flash_compiles(one_chip, cfg, seq):
    run = registry.get_spec("attention", "pallas_flash").fn
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    txt = _compiled_text(
        lambda q, k, v, n: run(q, k, v, kv_len=n, interpret=False), one_chip,
        ((1, seq, h, dh), BF16), ((1, seq, kvh, dh), BF16),
        ((1, seq, kvh, dh), BF16), ((1,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("cfg", ATTN_CFGS)
@pytest.mark.parametrize("impl", ["pallas_paged", "pallas_paged_q8"])
@pytest.mark.parametrize("b,width,page_size,ppb", [
    (8, 64, 16, 1), (8, 64, 32, 2),
    # the chat cell's decode: 128 slots, the widest table bucket, the
    # untuned block
    (128, 128, 16, None)])
def test_pallas_paged_compiles(one_chip, cfg, impl, b, width, page_size,
                               ppb):
    run = registry.get_spec("paged_decode", impl).fn
    pool = b * width + 1
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    page_dt = jnp.int8 if impl.endswith("_q8") else BF16
    shapes = [((b, 1, h, dh), BF16), ((pool, page_size, kvh, dh), page_dt),
              ((pool, page_size, kvh, dh), page_dt),
              ((b, width), jnp.int32), ((b,), jnp.int32),
              ((b, 1, kvh, dh), BF16), ((b, 1, kvh, dh), BF16)]
    if impl.endswith("_q8"):
        shapes += [((pool, page_size), jnp.float32)] * 2

        def fn(q, kp, vp, pt, ln, kn, vn, ks, vs):
            return run(q, kp, vp, pt, ln, kn, vn, k_scale=ks, v_scale=vs,
                       pages_per_block=ppb, interpret=False)
    else:
        def fn(q, kp, vp, pt, ln, kn, vn):
            return run(q, kp, vp, pt, ln, kn, vn, pages_per_block=ppb,
                       interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("cfg", ATTN_CFGS)
@pytest.mark.parametrize("dtype", [BF16, jnp.float32])
def test_sampling_argmax_compiles(one_chip, cfg, dtype):
    run = registry.get_spec("sampling", "pallas_greedy").fn
    txt = _compiled_text(lambda x: run(x, interpret=False), one_chip,
                         ((8, cfg.vocab), dtype))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("impl", ["pallas_paged", "pallas_paged_q8"])
def test_paged_vmem_estimate_bounds_compiled_kernel(one_chip, impl):
    """Held to exactly the VMEM gate's estimate, the paged kernel still
    compiles at a KVH=8 width (mistral-large-123b: 96 heads over 8 kv
    heads, head dim 128), so the estimate bounds what Mosaic allocates;
    a limit below one page tile is refused, so the limit is enforced."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.paged_decode import _paged_call
    cfg = get_arch("mistral-large-123b").config
    kvh, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kvh
    b, width, ps, ppb = 8, 64, 32, 2
    pool = b * width + 1
    q8 = impl.endswith("_q8")
    page_dt = jnp.int8 if q8 else BF16
    shapes = [((b, kvh, g, dh), BF16), ((pool, ps, kvh, dh), page_dt),
              ((pool, ps, kvh, dh), page_dt), ((b, width), jnp.int32),
              ((b,), jnp.int32), ((b, kvh, dh), BF16), ((b, kvh, dh), BF16)]
    if q8:
        shapes += [((pool, ps), jnp.float32)] * 2
    est = registry.get_spec("paged_decode", impl).tune.vmem(
        (ps, ppb), 2, g=g, dh=dh, kvh=kvh)

    def compiled(limit):
        params = pltpu.CompilerParams(vmem_limit_bytes=int(limit))

        def fn(q4, kp, vp, pt, ln, kn, vn, *scales):
            return _paged_call(q4, kp, vp, scales or None, pt, ln, kn, vn,
                               pages_per_block=ppb, interpret=False,
                               compiler_params=params)
        return _compiled_text(fn, one_chip, *shapes)

    assert "tpu_custom_call" in compiled(est)
    with pytest.raises(Exception, match="(?i)vmem"):
        compiled(ps * kvh * dh)


def _ssd_dims(arch):
    cfg = get_arch(arch).config
    if cfg.family == "hybrid":
        mc = cfg.mamba_config()
        return mc.d_state, mc.head_dim, cfg.chunk_size
    head = cfg.xlstm_config().head_dim
    return head, head, cfg.chunk_size


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "xlstm-350m"])
@pytest.mark.parametrize("seq", [300, 1024])
def test_pallas_ssd_compiles(one_chip, arch, seq):
    run = registry.get_spec("ssd_scan", "pallas_ssd").fn
    dk, dv, chunk = _ssd_dims(arch)
    b, h = 1, 4
    txt = _compiled_text(
        lambda q, k, v, lf, li: run(q, k, v, lf, li, chunk=chunk,
                                    interpret=False),
        one_chip, ((b, seq, h, dk), BF16), ((b, seq, h, dk), BF16),
        ((b, seq, h, dv), BF16), ((b, seq, h), jnp.float32),
        ((b, seq, h), jnp.float32))
    assert "tpu_custom_call" in txt


def test_decode_segment_compiles_at_full_width(one_chip, monkeypatch):
    """The whole qwen2-0.5b decode segment (24 layers, paged pool for 8
    slots x 2048 tokens) compiles for one chip and fits its HBM."""
    from repro.core.features import default_features
    from repro.models.lm import LM
    from repro.serve import Engine, ServeConfig
    # the registry decides from the backend: take its TPU branch, as on
    # the chip (this process's backend is the CPU)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lm = LM(QWEN, default_features().with_(remat_policy="none"), dtype=BF16)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   dtype=BF16))
    eng = Engine(lm, params, ServeConfig(page_size=16, batch_slots=8,
                                         max_seq=2048))
    state = jax.eval_shape(lambda: lm.init_decode_state(
        8, 2048, page_size=16, num_pages=eng.pool_pages,
        table_width=eng.table_width))
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = eng.decode_segment(8).lower(
        put(params), put(state),
        jax.ShapeDtypeStruct((8, QWEN.vocab), BF16, sharding=one_chip),
        put(key)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30, mem


def test_decode_segment_pool_copies_carry_kv_cache(one_chip, monkeypatch):
    """On the v5e the decode segment copies each layer's K/V pool slice,
    and the paged kernel's lane-dense view of it: every such copy of a
    whole layer's pool carries the ``kv_cache`` scope, and the kernel is
    one custom call per layer that keeps its name."""
    import re

    from repro.core.features import default_features
    from repro.models.lm import LM
    from repro.serve import Engine, ServeConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lm = LM(QWEN, default_features().with_(remat_policy="none"), dtype=BF16)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   dtype=BF16))
    eng = Engine(lm, params, ServeConfig(page_size=16, batch_slots=8,
                                         max_seq=512))
    state = jax.eval_shape(lambda: lm.init_decode_state(
        8, 512, page_size=16, num_pages=eng.pool_pages, table_width=8))
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    key = jax.eval_shape(lambda: jax.random.key(0))
    text = eng.decode_segment(2).lower(
        put(params), put(state),
        jax.ShapeDtypeStruct((8, QWEN.vocab), BF16, sharding=one_chip),
        put(key)).compile().as_text()
    p = eng.pool_pages
    layer_pool = re.compile(
        rf"%(copy|constant_dynamic-slice_fusion)[.\d]* = bf16\[(1,)?{p},16,")
    moved = [ln for ln in text.splitlines() if layer_pool.search(ln)]
    assert len(moved) >= 4
    assert all("/kv_cache/" in ln for ln in moved), moved
    # one kernel call in the layer scan's body, under the name the
    # benchmark's trace reduction keys
    kernel = re.compile(
        r"%paged_decode_attention_grouped\.\d+ = .*custom-call\(")
    assert len([ln for ln in text.splitlines() if kernel.search(ln)]) == 1


@pytest.mark.parametrize("m,k,n", [(1024, 4096, 1536), (1024, 1536, 4096),
                                   (11264, 4096, 1536), (576, 4096, 1536)],
                         ids=["decode_gate", "decode_down", "prefill_gate",
                              "prefill72_gate"])
def test_pallas_gmm_compiles(one_chip, m, k, n):
    """The grouped expert matmul at Qwen3-MoE's widths (d_model 4096,
    expert width 1536, 32 experts held): a decode step's 128 rows x top-8,
    a 1,408-token prefill's pairs, and a 72-token prefill's 576, which do
    not fill whole 128-row tiles."""
    run = registry.get_spec("grouped_matmul", "pallas_gmm").fn
    txt = _compiled_text(lambda a, b, g: run(a, b, g, interpret=False),
                         one_chip, ((m, k), BF16), ((32, k, n), BF16),
                         ((32,), jnp.int32))
    assert "tpu_custom_call" in txt and GMM.search(txt)


def test_moe_programs_compile_at_the_cut_widths(one_chip, monkeypatch):
    """The Qwen3-MoE stage's decode segment and its longest slot prefill
    (1,408 tokens), over a 2 GiB pool for 128 slots of 2,048 tokens,
    compile for one chip and fit its HBM, with the grouped expert matmul
    three times in the layer scan's body (gate, up, down) and the paged
    kernel once."""
    from repro.core.features import default_features
    from repro.models.lm import LM
    from repro.serve import Engine, ServeConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lm = LM(QWEN3_MOE, default_features().with_(remat_policy="none"),
            dtype=BF16)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   dtype=BF16))
    eng = Engine(lm, params, ServeConfig(page_size=16, batch_slots=128,
                                         max_seq=2048, pool_pages=10922))
    state = jax.eval_shape(lambda: lm.init_decode_state(
        128, 2048, **eng._state_kwargs()))
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    logits = arg((128, QWEN3_MOE.vocab), BF16)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = eng.decode_segment(8).lower(put(params), put(state), logits,
                                         put(key)).compile()
    with eng._impl_ctx():
        prefill = eng._paged_slot_prefill.lower(
            put(params), put(state), logits, arg((1, 1408), jnp.int32),
            arg((), jnp.int32), arg((eng.table_width,), jnp.int32),
            None).compile()
    for compiled, paged in ((decode, 1), (prefill, 0)):
        lines = compiled.as_text().splitlines()
        assert len([ln for ln in lines if GMM.search(ln)]) == 3
        assert len([ln for ln in lines if re.search(
            r"%paged_decode_attention_grouped\.\d+ = .*custom-call\(",
            ln)]) == paged
        mem = compiled.memory_analysis()
        used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        assert used < 15.75 * 2**30, mem
