"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the semantic contract of its kernel twin:

* :func:`stream_triad`      <- kernels/stream_triad.py
* :func:`jacobi7_valid`     <- kernels/jacobi7.py (T valid-mode sweeps)
* :func:`flash_attention`   <- kernels/flash_attention.py (causal GQA)
* :func:`ssd_scan`          <- kernels/ssd_scan.py (gated linear attention)
* :func:`grouped_matmul`    <- kernels/grouped_matmul.py (MoE expert FFNs)

All are deliberately naive/obvious implementations — correctness over
speed; tests sweep shapes/dtypes and assert_allclose kernels against these.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.linear_scan import sequential_linear_attention

__all__ = ["stream_triad", "jacobi7_sweep", "jacobi7_valid",
           "flash_attention", "paged_decode", "paged_decode_q8", "ssd_scan",
           "grouped_matmul"]


def stream_triad(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
                 s: float = 2.5) -> jnp.ndarray:
    """STREAM triad a = b + s*c (a participates only as the write stream)."""
    del a
    return b + s * c


def jacobi7_sweep(x: jnp.ndarray, omega: float = 1.0 / 6.0) -> jnp.ndarray:
    """One valid-mode 7-point Jacobi sweep: [X,Y,Z] -> [X-2,Y-2,Z-2]."""
    return omega * (
        x[:-2, 1:-1, 1:-1] + x[2:, 1:-1, 1:-1] +
        x[1:-1, :-2, 1:-1] + x[1:-1, 2:, 1:-1] +
        x[1:-1, 1:-1, :-2] + x[1:-1, 1:-1, 2:]
    )


def jacobi7_valid(x: jnp.ndarray, sweeps: int = 1,
                  omega: float = 1.0 / 6.0) -> jnp.ndarray:
    """T valid-mode sweeps (the wavefront kernel's contract): domain
    shrinks by 2 per dim per sweep — no boundary special cases."""
    for _ in range(sweeps):
        x = jacobi7_sweep(x, omega)
    return x


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, q_offset: int = 0,
                    kv_valid=None) -> jnp.ndarray:
    """GQA attention oracle.  q: [B,Sq,H,Dh]; k,v: [B,Sk,KVH,Dh].

    ``q_offset`` places query i at key position ``i + q_offset`` (cached
    prefill / decode segments where Sq != Sk); ``kv_valid`` (scalar or [B])
    masks keys at or past each row's valid KV length.  Rows with no valid
    key at all (kv_valid == 0) output exactly 0 — the kernel contract.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(dh)
    ok = jnp.ones((b, 1, 1, sq, sk), bool)
    if causal:
        mask = (jnp.arange(sk)[None, :]
                <= (jnp.arange(sq) + q_offset)[:, None])
        ok = ok & mask[None, None, None]
    if kv_valid is not None:
        kv_valid = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32), (b,))
        ok = ok & (jnp.arange(sk)[None, :]
                   < kv_valid[:, None])[:, None, None, None, :]
    scores = jnp.where(ok, scores, -2.0e38)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(ok.any(-1, keepdims=True), probs, 0.0).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, dh)


def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 page_table: jnp.ndarray, lengths: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray) -> jnp.ndarray:
    """Paged decode-attention oracle <- kernels/paged_decode.py.

    q: [B,1,H,Dh]; k/v_pages: [P,ps,KVH,Dh]; page_table: [B,NP] int32;
    lengths: [B] int32 (past tokens, new token excluded); k_new/v_new:
    [B,1,KVH,Dh].  Deliberately obvious: gather every listed page into a
    dense context, append the new token, run one full masked softmax.
    """
    b, _, h, dh = q.shape
    ps, kvh = k_pages.shape[1], k_pages.shape[2]
    np_w = page_table.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    k_ctx = k_pages[page_table].reshape(b, np_w * ps, kvh, dh)
    v_ctx = v_pages[page_table].reshape(b, np_w * ps, kvh, dh)
    k_full = jnp.concatenate([k_ctx, k_new.astype(k_ctx.dtype)], axis=1)
    v_full = jnp.concatenate([v_ctx, v_new.astype(v_ctx.dtype)], axis=1)
    sk = np_w * ps + 1
    # positional validity: context keys below each row's length, plus the
    # appended token itself (always valid) — not a causal triangle
    ok = jnp.concatenate(
        [jnp.arange(np_w * ps)[None, :] < lengths[:, None],
         jnp.ones((b, 1), bool)], axis=1)
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k_full.astype(q.dtype)).astype(jnp.float32)
    scores = scores / np.sqrt(dh)
    scores = jnp.where(ok[:, None, None, None, :], scores, -2.0e38)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_full.astype(q.dtype))
    return out.reshape(b, 1, h, dh)


def paged_decode_q8(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, page_table: jnp.ndarray,
                    lengths: jnp.ndarray, k_new: jnp.ndarray,
                    v_new: jnp.ndarray, *, k_scale: jnp.ndarray,
                    v_scale: jnp.ndarray) -> jnp.ndarray:
    """Quantized paged decode oracle <- kernels/paged_decode.py (q8).

    Same contract as :func:`paged_decode` over int8 pages: dequantize
    every gathered page row with its [P, ps] per-token f32 scale, then
    run the identical dense masked softmax.  The kernel must match this
    EXACTLY (the quantization error lives in the codes, not the kernel —
    dequant-then-attend is deterministic).
    """
    dq = q.dtype if q.dtype == jnp.float32 else jnp.float32
    k_deq = (k_pages.astype(jnp.float32)
             * k_scale.astype(jnp.float32)[..., None, None]).astype(dq)
    v_deq = (v_pages.astype(jnp.float32)
             * v_scale.astype(jnp.float32)[..., None, None]).astype(dq)
    return paged_decode(q, k_deq, v_deq, page_table, lengths, k_new, v_new)


def ssd_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
             log_f: jnp.ndarray, log_i: jnp.ndarray, *,
             normalize: bool = False,
             initial_state: Optional[Tuple] = None
             ) -> Tuple[jnp.ndarray, Tuple]:
    """Gated linear attention, O(S) sequential oracle.

    q,k: [B,S,H,dk]; v: [B,S,H,dv]; log_f/log_i: [B,S,H] (<= 0).
    Returns (y [B,S,H,dv], final_state (C [B,H,dk,dv], n [B,H,dk])).
    """
    return sequential_linear_attention(q, k, v, log_f, log_i,
                                       normalize=normalize,
                                       initial_state=initial_state)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes) -> jnp.ndarray:
    """Row r of group g (the ``group_sizes[g]`` rows after group g-1) times
    ``rhs[g]``, in float32; rows past the groups are 0."""
    sizes = np.asarray(group_sizes)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    start = 0
    for g, n in enumerate(sizes):
        rows = lhs[start:start + n].astype(jnp.float32)
        out = out.at[start:start + n].set(rows @ rhs[g].astype(jnp.float32))
        start += n
    return out
