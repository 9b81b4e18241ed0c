"""Chunked gated linear attention kernel (Pallas) — mLSTM / Mamba2 SSD.

Implements the contract of
:func:`repro.models.linear_scan.chunked_linear_attention` on a
(B*H, chunks) grid with the chunk dimension innermost: the inter-chunk
state C [dk,dv] and normalizer n [1,dk] persist in VMEM scratch across
chunk iterations (the recurrence), while the intra-chunk term is a pair of
MXU matmuls over the [c,c] decay-masked score tile — the SSD blocked
algorithm mapped to TPU (DESIGN.md §2).

Stability contract: log_f <= 0 and log_i <= 0 (enforced upstream by
log-sigmoid gates / dt folding), so every exponent is <= 0 and no running-
max stabilizer state is needed.

Oracle: kernels/ref.py::ssd_scan (sequential scan).

Registered as the ``ssd_scan`` family in kernels/registry.py
(``pallas_ssd`` — this kernel via ops.ssd_scan — vs the chunk-parallel
``jnp_scan`` twin); the chunk length is its tune space.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_flat"]


def _ssd_kernel(q_ref, k_ref, v_ref, lf_ref, li_ref, y_ref, c_out_ref,
                n_out_ref, C_ref, n_ref, *, c: int, normalize: bool,
                eps: float):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[...].astype(jnp.float32)              # [c, dk]
    k = k_ref[...].astype(jnp.float32)              # [c, dk]
    v = v_ref[...].astype(jnp.float32)              # [c, dv]
    # the gate blocks hold the row's whole [chunks, c] gate matrix; take
    # this chunk's row
    lf = lf_ref[pl.ds(j, 1), :].astype(jnp.float32)      # [1, c]
    li = li_ref[pl.ds(j, 1), :].astype(jnp.float32)      # [1, c]

    # Mosaic has no cumsum: the inclusive prefix sum Bc[t] = sum_{s<=t}
    # lf[s] is a matmul with the lower-triangular ones matrix, taken in
    # both orientations (column for the query side, row for the key
    # side), and li is turned into a column by the identity.  HIGHEST
    # keeps the f32 gates exact through the MXU.
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tri = col <= row
    nt = (((1,), (1,)), ((), ()))                   # A @ B^T
    hi = jax.lax.Precision.HIGHEST
    lower = tri.astype(jnp.float32)
    bc_col = jax.lax.dot_general(lower, lf, nt, precision=hi)   # [c, 1]
    bc_row = jax.lax.dot_general(lf, lower, nt, precision=hi)   # [1, c]
    li_col = jax.lax.dot_general((row == col).astype(jnp.float32), li, nt,
                                 precision=hi)                  # [c, 1]
    total = jnp.sum(lf, axis=1, keepdims=True)      # [1, 1]

    # inter-chunk: contribution of the carried state
    qd = q * jnp.exp(bc_col)                        # [c, dk]
    y_inter = jax.lax.dot(qd, C_ref[...])           # [c, dv]
    n_inter = jax.lax.dot_general(qd, n_ref[...], nt)   # [c, 1]

    # intra-chunk: decay-masked attention
    gap = bc_col - bc_row + li                      # [c, c]
    A = jnp.where(tri, jnp.exp(gap), 0.0)
    scores = jax.lax.dot_general(q, k, nt) * A
    y = y_inter + jax.lax.dot(scores, v)
    if normalize:
        denom = jnp.abs(n_inter + jnp.sum(scores, axis=1, keepdims=True))
        y = y / jnp.maximum(denom, eps)
    y_ref[...] = y.astype(y_ref.dtype)

    # state update
    kw = k * jnp.exp(total - bc_col + li_col)       # [c, dk]
    C_ref[...] = jnp.exp(total) * C_ref[...] + \
        jax.lax.dot_general(kw, v, (((0,), (0,)), ((), ())))
    n_ref[...] = jnp.exp(total) * n_ref[...] + \
        jnp.sum(kw, axis=0, keepdims=True)

    @pl.when(j == nj - 1)
    def _finish():
        c_out_ref[...] = C_ref[...]
        n_out_ref[...] = n_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "normalize", "eps",
                                             "interpret"))
def ssd_scan_flat(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  log_f: jnp.ndarray, log_i: jnp.ndarray, *,
                  chunk: int = 128, normalize: bool = False,
                  eps: float = 1e-6, interpret: bool | None = None
                  ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Flat layout: q,k [BH,S,dk]; v [BH,S,dv]; log_f/log_i [BH,S].

    Returns (y [BH,S,dv], (C [BH,dk,dv], n [BH,1,dk])).
    S is padded to a chunk multiple with log_i = -1e9 (inert writes).
    """
    if interpret is None:
        from repro.kernels.registry import default_interpret
        interpret = default_interpret()
    bh, s, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v = zp(q), zp(k), zp(v)
        log_f = zp(log_f)
        log_i = jnp.pad(log_i, ((0, 0), (0, pad)), constant_values=-1e9)
    nc = q.shape[1] // c
    # gates as [BH, chunks, c] with one whole-row block per BH: a (1, c)
    # block of [BH, S] puts 1 in the sublane dim, which the TPU tiling
    # refuses; a full [chunks, c] block is always accepted.  f32, because
    # the kernel selects its chunk's row at a dynamic sublane offset,
    # which packed bf16 rows cannot serve (the kernel computes in f32
    # anyway, so the widening changes nothing)
    log_f = log_f.astype(jnp.float32).reshape(bh, nc, c)
    log_i = log_i.astype(jnp.float32).reshape(bh, nc, c)
    y, c_out, n_out = pl.pallas_call(
        functools.partial(_ssd_kernel, c=c, normalize=normalize, eps=eps),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, c, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, c, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, c, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, nc, c), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, nc, c), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, c, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, dk, dv), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, dk), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc * c, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, dk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, log_f, log_i)
    return y[:, :s], (c_out, n_out)
