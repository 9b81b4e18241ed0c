"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

On a CPU backend every kernel runs in interpret mode; on a TPU the same
pallas_call compiles (``registry.default_interpret`` decides from the
backend).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.jacobi7 import jacobi7_naive, jacobi7_wavefront
from repro.kernels.ssd_scan import ssd_scan_flat
from repro.kernels.stream_triad import stream_triad, triad_bytes

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# STREAM triad (paper case study 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 4096, 128 * 513])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pipelined", [True, False])
def test_stream_triad_sweep(n, dtype, pipelined):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n))
    b, c = _rand(k1, (n,), dtype), _rand(k2, (n,), dtype)
    out = stream_triad(b, c, s=2.5, pipelined=pipelined)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.stream_triad(None, b, c, 2.5), np.float32),
        **TOL[dtype])


def test_stream_triad_rejects_unaligned():
    with pytest.raises(AssertionError):
        stream_triad(jnp.ones((100,)), jnp.ones((100,)))


def test_triad_bytes_model():
    assert triad_bytes(1024) == 3 * 1024 * 4


# ---------------------------------------------------------------------------
# Jacobi 7-point stencil (paper case studies 2+3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(10, 18, 130), (18, 34, 130), (12, 20, 258)])
def test_jacobi7_naive_sweep(shape):
    x = _rand(jax.random.PRNGKey(1), shape)
    np.testing.assert_allclose(jacobi7_naive(x), ref.jacobi7_sweep(x),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("sweeps", [1, 2, 3])
def test_jacobi7_wavefront_temporal_blocking(sweeps):
    """The wavefront kernel fuses `sweeps` Jacobi iterations in VMEM —
    results must equal `sweeps` separate naive sweeps (oracle)."""
    x = _rand(jax.random.PRNGKey(2), (16, 26, 130))
    got = jacobi7_wavefront(x, sweeps=sweeps)
    np.testing.assert_allclose(got, ref.jacobi7_valid(x, sweeps),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_jacobi7_wavefront_equals_composed_naive():
    x = _rand(jax.random.PRNGKey(3), (14, 22, 130))
    two_naive = jacobi7_naive(jacobi7_naive(x))
    np.testing.assert_allclose(jacobi7_wavefront(x, sweeps=2), two_naive,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Flash attention (blockwise; LM hot spot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kvh,dh", [
    (1, 128, 4, 4, 32),     # MHA
    (2, 256, 4, 2, 32),     # GQA 2:1
    (1, 256, 8, 1, 64),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kvh, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 3)
    q = _rand(ks[0], (b, s, h, dh), dtype)
    k = _rand(ks[1], (b, s, kvh, dh), dtype)
    v = _rand(ks[2], (b, s, kvh, dh), dtype)
    got = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (1, 128, 2, 32))
    k = _rand(ks[1], (1, 128, 2, 32))
    v = _rand(ks[2], (1, 128, 2, 32))
    got = ops.flash_attention(q, k, v, causal=False, bq=64, bk=64)
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shape_invariance(bq, bk):
    """Block shape is a perf knob, never a semantics knob."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(ks[0], (1, 256, 2, 32))
    k = _rand(ks[1], (1, 256, 2, 32))
    v = _rand(ks[2], (1, 256, 2, 32))
    got = ops.flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# --- serving shapes: causal offsets (sq != sk) x ragged KV x GQA ----------
#
# The kernel used to be WRONG here: no q_offset meant causal masking
# assumed query 0 sits at key 0, and ragged/unaligned sk was an assert.
# Both the Pallas kernel and the jnp flash twin must now match the dense
# oracle at fp32 tightness (the acceptance bar: atol 1e-5).

@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (8, 1)])   # MHA/GQA/MQA
@pytest.mark.parametrize("sq,sk,ragged", [
    (64, 160, False),     # multi-token decode segment: queries end at sk
    (96, 96, True),       # self-attention prefill over right-padded rows
    (64, 200, True),      # cached prefill: offset + ragged + unaligned sk
])
def test_flash_offset_ragged_gqa_parity(h, kvh, sq, sk, ragged):
    from repro.models.attention import _flash_attention_offset

    ks = jax.random.split(jax.random.PRNGKey(sq + sk + h), 3)
    q = _rand(ks[0], (2, sq, h, 32))
    k = _rand(ks[1], (2, sk, kvh, 32))
    v = _rand(ks[2], (2, sk, kvh, 32))
    kv_len = jnp.array([sk, sk - 29], jnp.int32) if ragged else None
    q_offset = sk - sq
    want = ref.flash_attention(q, k, v, causal=True, q_offset=q_offset,
                               kv_valid=kv_len)
    got_pallas = ops.flash_attention(q, k, v, causal=True,
                                     q_offset=q_offset, kv_valid=kv_len,
                                     bq=32, bk=64, interpret=True)
    got_twin = _flash_attention_offset(q, k, v, q_offset, True,
                                       k_chunk=64, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got_pallas), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_twin), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_noncausal_ragged_no_longer_asserts():
    """Unaligned/ragged sk used to be `assert causal` — now masked in-kernel."""
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = _rand(ks[0], (2, 96, 4, 32))
    k = _rand(ks[1], (2, 200, 2, 32))      # 200 % bk != 0
    v = _rand(ks[2], (2, 200, 2, 32))
    kv_len = jnp.array([200, 73], jnp.int32)
    got = ops.flash_attention(q, k, v, causal=False, kv_valid=kv_len,
                              bq=32, bk=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=False, kv_valid=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_zero_valid_rows_output_zero():
    """kv_valid == 0 rows produce exactly 0 (not a softmax over nothing)."""
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q = _rand(ks[0], (2, 64, 2, 32))
    k = _rand(ks[1], (2, 64, 2, 32))
    v = _rand(ks[2], (2, 64, 2, 32))
    kv_len = jnp.array([0, 64], jnp.int32)
    got = ops.flash_attention(q, k, v, causal=False, kv_valid=kv_len,
                              bq=32, bk=32, interpret=True)
    assert float(jnp.abs(got[0]).max()) == 0.0
    np.testing.assert_allclose(
        np.asarray(got[1]),
        np.asarray(ref.flash_attention(q, k, v, causal=False)[1]),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bq,bk", [(32, 32), (32, 64), (64, 32)])
def test_flash_offset_block_shape_invariance(bq, bk):
    """Tiling stays a pure perf knob with offsets and ragged KV in play."""
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = _rand(ks[0], (2, 64, 4, 32))
    k = _rand(ks[1], (2, 160, 2, 32))
    v = _rand(ks[2], (2, 160, 2, 32))
    kv_len = jnp.array([150, 97], jnp.int32)
    got = ops.flash_attention(q, k, v, causal=True, q_offset=96,
                              kv_valid=kv_len, bq=bq, bk=bk, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, q_offset=96,
                               kv_valid=kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# SSD / gated linear-attention chunk scan (Mamba2 + mLSTM hot spot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,dk,dv,chunk", [
    (1, 128, 2, 16, 16, 32),
    (2, 256, 2, 16, 32, 64),
    (1, 64, 4, 32, 32, 64),    # chunk == seq
])
def test_ssd_scan_sweep(b, s, h, dk, dv, chunk):
    ks = jax.random.split(jax.random.PRNGKey(s + dk), 5)
    q = _rand(ks[0], (b, s, h, dk))
    k = _rand(ks[1], (b, s, h, dk))
    v = _rand(ks[2], (b, s, h, dv))
    log_f = -jax.nn.softplus(_rand(ks[3], (b, s, h)))
    log_i = -jax.nn.softplus(_rand(ks[4], (b, s, h)))
    y, (C, n) = ops.ssd_scan(q, k, v, log_f, log_i, chunk=chunk)
    y_ref, (C_ref, n_ref) = ref.ssd_scan(q, k, v, log_f, log_i)
    np.testing.assert_allclose(y, y_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(C, C_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(n, n_ref, rtol=2e-3, atol=2e-3)


def test_ssd_scan_chunk_invariance():
    """Chunk size must not change semantics (associativity of the scan)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    b, s, h, d = 1, 128, 2, 16
    q = _rand(ks[0], (b, s, h, d)); k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    lf = -jax.nn.softplus(_rand(ks[3], (b, s, h)))
    li = -jax.nn.softplus(_rand(ks[4], (b, s, h)))
    y32, _ = ops.ssd_scan(q, k, v, lf, li, chunk=32)
    y64, _ = ops.ssd_scan(q, k, v, lf, li, chunk=64)
    np.testing.assert_allclose(y32, y64, rtol=2e-3, atol=2e-3)


def test_ssd_scan_normalized_mode():
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    b, s, h, d = 1, 64, 2, 16
    q = _rand(ks[0], (b, s, h, d)); k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    lf = -jax.nn.softplus(_rand(ks[3], (b, s, h)))
    li = -jax.nn.softplus(_rand(ks[4], (b, s, h)))
    y, _ = ops.ssd_scan(q, k, v, lf, li, chunk=32, normalize=True)
    y_ref, _ = ref.ssd_scan(q, k, v, lf, li, normalize=True)
    np.testing.assert_allclose(y, y_ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# grouped matmul (the MoE layer's expert FFNs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["pallas_gmm", "jnp_gmm"])
@pytest.mark.parametrize("sizes", [(5, 0, 20, 3), (0, 0, 0, 0), (40, 0, 0, 0),
                                   (1, 1, 1, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(impl, sizes, dtype):
    """Rows grouped by expert, with empty groups and rows past the groups
    (undefined for the kernel, so compared only inside them)."""
    from repro.kernels import registry
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(sizes)))
    lhs, rhs = _rand(k1, (48, 32), dtype), _rand(k2, (4, 32, 24), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = registry.run("grouped_matmul", lhs, rhs, gs, impl=impl)
    n = sum(sizes)
    assert got.dtype == jnp.float32 and got.shape == (48, 24)
    np.testing.assert_allclose(np.asarray(got[:n]),
                               np.asarray(ref.grouped_matmul(lhs, rhs, gs)[:n]),
                               **TOL[jnp.float32])


@pytest.mark.parametrize("impl", ["pallas_gmm", "jnp_gmm"])
def test_grouped_matmul_rows_not_a_whole_tile(impl):
    """200 rows (a 128-row tile and part of another), groups crossing the
    tile boundary and ending short of the last row: a prefill whose pairs
    do not fill the kernel's last row tile."""
    from repro.kernels import registry
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    lhs, rhs = _rand(k1, (200, 32), jnp.bfloat16), \
        _rand(k2, (4, 32, 24), jnp.bfloat16)
    gs = jnp.asarray((60, 0, 100, 30), jnp.int32)
    got = registry.run("grouped_matmul", lhs, rhs, gs, impl=impl)
    assert got.shape == (200, 24)
    np.testing.assert_allclose(np.asarray(got[:190]),
                               np.asarray(ref.grouped_matmul(lhs, rhs,
                                                             gs)[:190]),
                               **TOL[jnp.float32])
