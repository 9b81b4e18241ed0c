"""The while-aware HLO static analyzer — the 'MSR read' layer of perfctr.

The critical properties:

1. on scan-free programs our FLOPs/bytes match XLA's own cost_analysis;
2. a scanned program and its unrolled twin get the SAME dynamic cost
   (XLA's raw numbers differ by the trip count — the bug this module fixes);
3. collectives inside scan bodies are counted trip_count times.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core.events import normalize_cost
from repro.core.hlo_cost import (analyze_text, parse_module, shape_bytes,
                                 shape_elems)


# ---------------------------------------------------------------------------
# shape parsing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,elems,bytes_", [
    ("f32[8,128]{1,0}", 1024, 4096),
    ("bf16[2,3,4]", 24, 48),
    ("pred[]", 1, 1),
    ("s32[]", 1, 4),
    ("(f32[8]{0}, bf16[4])", 12, 40),
    ("u8[16]", 16, 16),
])
def test_shape_parsing(s, elems, bytes_):
    assert shape_elems(s) == elems
    assert shape_bytes(s) == bytes_


def test_parse_module_tuple_shapes_with_index_comments():
    # the /*index=N*/ comments inside tuple shapes broke a regex once
    txt = """
HloModule m

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %t = (f32[4]{0}, /*index=1*/s32[], f32[2,2]{1,0}) tuple(%a, %a, %a)
  ROOT %r = f32[4]{0} get-tuple-element(%t), index=0
}
"""
    mod = parse_module(txt)
    assert mod.entry == "main"
    comp = mod.computations["main"]
    ops = [i.op for i in comp.instructions]
    assert ops == ["parameter", "tuple", "get-tuple-element"]
    assert comp.instructions[1].shape.startswith("(f32[4]")


# ---------------------------------------------------------------------------
# agreement with XLA on scan-free programs
# ---------------------------------------------------------------------------

def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matches_xla_on_unrolled_matmul_chain():
    def f(x, w):
        y = x
        for i in range(w.shape[0]):
            y = jnp.maximum(y @ w[i], 0.0)
        return y.sum()

    x = jnp.ones((16, 64), jnp.float32)
    w = jnp.ones((6, 64, 64), jnp.float32)
    c = _compile(f, x, w)
    got = analyze_text(c.as_text())
    ca = normalize_cost(c.cost_analysis())
    assert got.flops == pytest.approx(ca["flops"], rel=0.01)
    assert got.bytes_accessed == pytest.approx(ca["bytes accessed"], rel=0.05)


def test_matches_xla_dot_flops_batched():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jnp.ones((4, 8, 16), jnp.float32)
    b = jnp.ones((4, 16, 32), jnp.float32)
    c = _compile(f, a, b)
    got = analyze_text(c.as_text())
    # 2 * B*M*N*K
    assert got.flops == pytest.approx(2 * 4 * 8 * 32 * 16, rel=0.05)
    assert got.flops == pytest.approx(
        normalize_cost(c.cost_analysis())["flops"], rel=0.05)


# ---------------------------------------------------------------------------
# the while fix itself
# ---------------------------------------------------------------------------

def _scan_fn(x, w):
    def body(c, wi):
        return jnp.maximum(c @ wi, 0.0), None
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()


def _unroll_fn(x, w):
    y = x
    for i in range(w.shape[0]):
        y = jnp.maximum(y @ w[i], 0.0)
    return y.sum()


def test_scanned_equals_unrolled_dynamic_cost():
    x = jnp.ones((16, 64), jnp.float32)
    w = jnp.ones((24, 64, 64), jnp.float32)
    ds = analyze_text(_compile(_scan_fn, x, w).as_text())
    du = analyze_text(_compile(_unroll_fn, x, w).as_text())
    assert ds.flops == pytest.approx(du.flops, rel=0.02)
    assert ds.bytes_accessed == pytest.approx(du.bytes_accessed, rel=0.05)


def test_xla_raw_undercounts_scan_ours_does_not():
    """Documents the bug being fixed: XLA counts the while body once."""
    x = jnp.ones((16, 64), jnp.float32)
    w = jnp.ones((24, 64, 64), jnp.float32)
    c = _compile(_scan_fn, x, w)
    raw = normalize_cost(c.cost_analysis())["flops"]
    dyn = analyze_text(c.as_text())
    assert dyn.flops > 10 * raw          # 24 iterations vs 1
    assert any(t == 24.0 for t in dyn.while_trips.values())


def test_trip_count_from_backend_config():
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((7, 8, 8), jnp.float32)
    dyn = analyze_text(_compile(_scan_fn, x, w).as_text())
    assert 7.0 in dyn.while_trips.values()


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.maximum(ci @ wi, 0.0), None
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, w)
        return y.sum()

    x = jnp.ones((16, 32), jnp.float32)
    w = jnp.ones((3, 32, 32), jnp.float32)
    dyn = analyze_text(_compile(f, x, w).as_text())
    # 3 * 5 matmuls of 2*16*32*32
    assert dyn.flops == pytest.approx(15 * 2 * 16 * 32 * 32, rel=0.10)


def test_transcendentals_counted():
    def f(x):
        return jnp.exp(x).sum()

    x = jnp.ones((128,), jnp.float32)
    c = _compile(f, x)
    dyn = analyze_text(c.as_text())
    assert dyn.transcendentals == pytest.approx(128, rel=0.01)


def test_op_counts_sees_whiles_and_dots():
    x = jnp.ones((16, 64), jnp.float32)
    w = jnp.ones((4, 64, 64), jnp.float32)
    dyn = analyze_text(_compile(_scan_fn, x, w).as_text())
    assert dyn.op_counts.get("while", 0) >= 1
    assert dyn.op_counts.get("dot", 0) >= 1


def test_slice_charged_at_window_not_operand():
    def f(w):
        return w[3].sum()           # slices one [64,64] out of [24,64,64]

    w = jnp.ones((24, 64, 64), jnp.float32)
    dyn = analyze_text(_compile(f, w).as_text())
    # traffic must be ~2x the 16 KiB window + reduction, nowhere near 393 KiB
    assert dyn.bytes_accessed < 100_000


# ---------------------------------------------------------------------------
# the paged path: gather/dynamic-slice index operands are charged
# ---------------------------------------------------------------------------

def test_gather_charges_index_operand_bytes():
    """Hand-written paged-KV gather HLO pins the byte model exactly:
    2x the gathered window + the page-table indices — NOT the pool."""
    txt = """
HloModule paged

ENTRY %main (pool: f32[33,16,2,32], table: s32[4,8]) -> f32[4,8,16,2,32] {
  %pool = f32[33,16,2,32]{3,2,1,0} parameter(0)
  %table = s32[4,8]{1,0} parameter(1)
  ROOT %g = f32[4,8,16,2,32]{4,3,2,1,0} gather(%pool, %table), offset_dims={2,3,4}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=2, slice_sizes={1,16,2,32}
}
"""
    dyn = analyze_text(txt)
    window = 4 * 8 * 16 * 2 * 32 * 4          # the gathered result, f32
    table = 4 * 8 * 4                          # s32 page-table read
    assert dyn.bytes_accessed == pytest.approx(2 * window + table)


def test_dynamic_slice_charges_start_index_operands():
    txt = """
HloModule ds

ENTRY %main (buf: f32[128,64], i: s32[], j: s32[]) -> f32[8,64] {
  %buf = f32[128,64]{1,0} parameter(0)
  %i = s32[] parameter(1)
  %j = s32[] parameter(2)
  ROOT %w = f32[8,64]{1,0} dynamic-slice(%buf, %i, %j), dynamic_slice_sizes={8,64}
}
"""
    dyn = analyze_text(txt)
    assert dyn.bytes_accessed == pytest.approx(2 * 8 * 64 * 4 + 2 * 4)


def test_paged_decode_bytes_track_table_width_not_pool():
    """Compiled regression: the jnp paged decode reference's modeled
    traffic scales with the gathered window (table_width * page_size),
    not the pool size — doubling the POOL leaves bytes untouched, while
    doubling the TABLE roughly doubles them."""
    from repro.models.attention import paged_decode_jnp

    def compile_bytes(p_total, np_w):
        B, H, KVH, Dh, ps = 4, 4, 2, 32, 16
        args = (jax.ShapeDtypeStruct((B, 1, H, Dh), jnp.float32),
                jax.ShapeDtypeStruct((1, p_total, ps, KVH * Dh),
                                     jnp.float32),
                jax.ShapeDtypeStruct((1, p_total, ps, KVH * Dh),
                                     jnp.float32),
                jax.ShapeDtypeStruct((B, np_w), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B, 1, KVH, Dh), jnp.float32),
                jax.ShapeDtypeStruct((B, 1, KVH, Dh), jnp.float32))
        c = jax.jit(paged_decode_jnp).lower(*args).compile()
        return analyze_text(c.as_text()).bytes_accessed

    base = compile_bytes(33, 8)
    double_pool = compile_bytes(65, 8)
    double_table = compile_bytes(65, 16)
    assert double_pool == pytest.approx(base, rel=0.02)
    assert double_table > 1.6 * base


def test_fusion_scatter_destination_is_in_place():
    """A fused scatter whose destination aliases a fusion param (the paged
    token write on TPU-style HLO) charges update+index traffic, not a
    full-pool round trip per visit."""
    txt = """
HloModule ps

%assign (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  ROOT %b = f32[] parameter(1)
}

%fused_scatter (p0: f32[256,16,64], p1: s32[2,1], p2: f32[2,16,64]) -> f32[256,16,64] {
  %p0 = f32[256,16,64]{2,1,0} parameter(0)
  %p1 = s32[2,1]{1,0} parameter(1)
  %p2 = f32[2,16,64]{2,1,0} parameter(2)
  ROOT %sc = f32[256,16,64]{2,1,0} scatter(%p0, %p1, %p2), update_window_dims={1,2}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%assign
}

ENTRY %main (pool: f32[256,16,64], ids: s32[2,1], upd: f32[2,16,64]) -> f32[256,16,64] {
  %pool = f32[256,16,64]{2,1,0} parameter(0)
  %ids = s32[2,1]{1,0} parameter(1)
  %upd = f32[2,16,64]{2,1,0} parameter(2)
  ROOT %f = f32[256,16,64]{2,1,0} fusion(%pool, %ids, %upd), kind=kLoop, calls=%fused_scatter
}
"""
    dyn = analyze_text(txt)
    upd = 2 * 16 * 64 * 4
    idx = 2 * 1 * 4
    # write: update region + indices; read: indices + updates; pool: 0
    assert dyn.bytes_accessed == pytest.approx(upd + idx + idx + upd)
    assert dyn.bytes_accessed < 256 * 16 * 64 * 4 / 10
