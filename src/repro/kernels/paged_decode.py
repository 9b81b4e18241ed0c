"""Paged decode attention (Pallas, TPU-targeted): O(length) bytes/token.

The decode hot path used to score the ENTIRE [B, max_seq, KVH, Dh] cache
buffer every token and mask — bytes/token was O(max_seq) even for rows
holding 30 tokens of context.  This kernel walks each row's *page table*
instead: the KV cache lives in a pool of fixed-size pages
(``serve/kv_pool.py``), each row owns exactly ``ceil(length / page_size)``
of them, and decode touches only those.

Structure (grid = batch x page-blocks, page-blocks innermost; the kv
heads of a page are one lane-dense ``[ps, KVH*Dh]`` tile):

* the page table ``[B, NP]`` and per-row lengths ``[B]`` are scalar-
  prefetched (``pltpu.PrefetchScalarGridSpec``), so the k/v BlockSpec
  index maps translate *logical* page j of row b to its *physical* page
  ``pt[b, j]`` before the DMA is issued — the gather happens in the
  pipeline, no materialized gathered copy;
* dead logical pages (``j * page_size >= length[b]``) clamp their index
  map to the row's last live page — consecutive grid steps then request
  the SAME block, which the pipeline does not re-fetch — and skip their
  matmuls entirely via ``pl.when``;
* online softmax state (running max / denominator / accumulator) lives in
  VMEM scratch across the page-block iterations; at the last block the
  NEW token's K/V (one [KVH, Dh] row, passed separately so the caller can
  scatter it into its page afterwards) is folded into the same softmax
  and the output normalized — the exact two-part-softmax contract of
  ``models/attention.py::decode_attention_token``;
* ``pages_per_block`` fetches that many pages per grid step (each its own
  BlockSpec, so non-contiguous physical pages still pipeline); together
  with ``page_size`` it is the tile knob ``kernels/autotune.py`` sweeps.

Layout contract: q grouped [B, KVH, G, Dh]; pages [P, page_size, KVH, Dh]
(the pool layout, one layer's slice).  ``paged_decode_attention`` adapts
from the model's [B, 1, H, Dh].  Oracle: kernels/ref.py::paged_decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_attention_grouped",
           "paged_decode_attention_q8", "paged_decode_attention_q8_grouped"]

NEG_INF = -2.0e38


def _paged_kernel(lens_ref, pt_ref, q_ref, *refs, scale: float, ps: int,
                  ppb: int, kvh: int, dh: int, quantized: bool):
    """refs: k_0..k_{ppb-1}, v_0.., [ksc_0.., vsc_0.. when quantized],
    k_new, v_new, o, m, l, acc.

    Pages arrive lane-dense as ``[ps, KVH*Dh]`` tiles (every kv head of
    one page in one DMA).  Each kv head's queries sit block-diagonally in
    a ``[G, KVH*Dh]`` row (zeros outside the head's lanes), so a full-width
    matmul scores exactly that head's keys; the per-head accumulators are
    full width too, and the finish keeps each head's own lanes.  No lane
    slice at a sub-128 offset is ever taken.
    """
    n_in = (4 if quantized else 2) * ppb
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    ksc_refs = refs[2 * ppb:3 * ppb]
    vsc_refs = refs[3 * ppb:4 * ppb]
    kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref = refs[n_in:]
    b = pl.program_id(0)
    j = pl.program_id(1)                  # page block (innermost, sequential)
    njb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]                  # this row's past-token count

    for i in range(ppb):
        p = j * ppb + i                   # logical page index

        # dead pages (entirely past this row's context) skip both matmuls;
        # their index map already clamps to a live page, so no new DMA
        # was issued for them either
        @pl.when(p * ps < length)
        def _accumulate(i=i, p=p):
            k = k_refs[i][...].astype(jnp.float32)        # [ps, W]
            v = v_refs[i][...].astype(jnp.float32)        # [ps, W]
            if quantized:
                # dequant in VMEM: int8 codes x f32 per-token scales [ps, 1]
                k = k * ksc_refs[i][...]
                v = v * vsc_refs[i][...]
            for h in range(kvh):
                q = q_ref[h].astype(jnp.float32) * scale  # [G, W]
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
                kpos = p * ps + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                ok = kpos < length        # partial last page
                s = jnp.where(ok, s, NEG_INF)
                m_prev = m_ref[h]                         # [G, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(pr, v)
                m_ref[h] = m_new

    @pl.when(j == njb - 1)
    def _fold_token_and_finish():
        # the new token attends itself: fold its single K/V row into the
        # running softmax, then normalize — rows with length == 0 (empty
        # slots) come through here with (m, l, acc) untouched and output
        # exactly softmax over {the token} = v_new
        kt = kn_ref[...].astype(jnp.float32)              # [1, W]
        vt = vn_ref[...].astype(jnp.float32)              # [1, W]
        head = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1) // dh
        out = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(kvh):
            q = q_ref[h].astype(jnp.float32) * scale
            s_t = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())))
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s_t)
            alpha = jnp.exp(m_prev - m_new)
            p_t = jnp.exp(s_t - m_new)
            l = l_ref[h] * alpha + p_t
            acc = acc_ref[h] * alpha + p_t * vt
            out = jnp.where(head == h, acc / jnp.maximum(l, 1e-20), out)
        o_ref[...] = out.astype(o_ref.dtype)


def _paged_call(q4, k_pages, v_pages, scales, page_table, lengths, k_new,
                v_new, *, pages_per_block: int, interpret,
                compiler_params=None):
    """Shared pallas_call for the fp and int8 page flavors.

    ``scales`` is None (fp pages) or ``(k_scale, v_scale)`` ``[P, ps]``.
    The pool ``[P, ps, KVH, Dh]`` is viewed as ``[P, ps, KVH*Dh]`` (a
    reshape, though not a free one on a v5e): the block's minor dim is
    then the whole lane-dense row, which the TPU tiling accepts for any
    KVH and Dh — a per-head block would put KVH in the sublane dim,
    refused unless KVH is a multiple of 8.  ``compiler_params`` (``pltpu.CompilerParams``) passes through
    to the ``pallas_call``, e.g. to hold the kernel to a VMEM limit.
    """
    if interpret is None:
        from repro.kernels.registry import default_interpret
        interpret = default_interpret()
    b, kvh, g, dh = q4.shape
    p_total, ps, kvh_p, _ = k_pages.shape
    assert kvh_p == kvh, (kvh_p, kvh)
    w = kvh * dh
    np_w = page_table.shape[1]
    ppb = max(1, min(pages_per_block, np_w))
    njb = -(-np_w // ppb)
    lengths = jnp.asarray(lengths, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    # block-diagonal queries: head h's [G, Dh] in lanes [h*Dh, (h+1)*Dh)
    eye = jnp.eye(kvh, dtype=q4.dtype)
    qw = (eye[None, :, None, :, None] * q4[:, :, :, None, :]).reshape(
        b, kvh, g, w)
    kn = k_new.reshape(b, 1, w)
    vn = v_new.reshape(b, 1, w)

    def page_map(i, *trail):
        # logical page j*ppb+i of row b -> physical page, clamped to the
        # row's last LIVE page so dead grid steps re-request the block
        # already resident (the pipeline elides the copy)
        def imap(b_, j_, lens, pt):
            p_log = j_ * ppb + i
            live = jnp.maximum((lens[b_] + ps - 1) // ps - 1, 0)
            p_eff = jnp.minimum(jnp.minimum(p_log, np_w - 1), live)
            return (pt[b_, p_eff], 0) + trail
        return imap

    kv_specs = [pl.BlockSpec((None, ps, w), page_map(i, 0))
                for i in range(ppb)]
    # on a v5e the [ps, KVH, Dh] and [ps, W] tilings differ, so XLA
    # copies the whole pool to make this view: pool movement outside the
    # kernel, named as such
    with jax.named_scope("kv_cache"):
        k_rows = k_pages.reshape(p_total, ps, w)
        v_rows = v_pages.reshape(p_total, ps, w)
    operands = [k_rows] * ppb + [v_rows] * ppb
    in_pages = kv_specs * 2
    if scales is not None:
        # [P, ps] -> [P, ps, 1]: the in-kernel scale block is a [ps, 1]
        # column broadcasting over the page's [ps, W] codes
        sc_specs = [pl.BlockSpec((None, ps, 1), page_map(i, 0))
                    for i in range(ppb)]
        in_pages += sc_specs * 2
        operands += [scales[0].astype(jnp.float32)[..., None]] * ppb \
            + [scales[1].astype(jnp.float32)[..., None]] * ppb
    row = lambda b_, j_, lens, pt: (b_, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # lengths, page_table
        grid=(b, njb),
        in_specs=[
            pl.BlockSpec((None, kvh, g, w),
                         lambda b_, j_, lens, pt: (b_, 0, 0, 0)),
            *in_pages,
            pl.BlockSpec((None, 1, w), row),
            pl.BlockSpec((None, 1, w), row),
        ],
        out_specs=pl.BlockSpec((None, g, w), row),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running max
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # denominator
            pltpu.VMEM((kvh, g, w), jnp.float32),     # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=1.0 / (dh ** 0.5),
                               ps=ps, ppb=ppb, kvh=kvh, dh=dh,
                               quantized=scales is not None)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, w), q4.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(lengths, page_table, qw, *operands, kn, vn)
    # [B, G, KVH*Dh] -> [B, KVH, G, Dh]
    return out.reshape(b, g, kvh, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention_grouped(q4: jnp.ndarray, k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   page_table: jnp.ndarray,
                                   lengths: jnp.ndarray,
                                   k_new: jnp.ndarray, v_new: jnp.ndarray, *,
                                   pages_per_block: int = 1,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """q4: [B,KVH,G,Dh]; k/v_pages: [P,ps,KVH,Dh]; page_table: [B,NP] int32;
    lengths: [B] int32 (past tokens; the new token is NOT in the pages yet);
    k_new/v_new: [B,KVH,Dh].  Returns [B,KVH,G,Dh].

    ``page_table[b, j]`` is the physical page holding row b's tokens
    ``[j*ps, (j+1)*ps)``; entries past ``ceil(lengths[b]/ps)`` are never
    read (their index maps clamp to the last live page, their compute is
    skipped).  Physical page 0 is the pool's null page by convention —
    rows with ``lengths[b] == 0`` resolve to it but accumulate nothing.
    """
    return _paged_call(q4, k_pages, v_pages, None, page_table, lengths,
                       k_new, v_new, pages_per_block=pages_per_block,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention_q8_grouped(q4: jnp.ndarray, k_pages: jnp.ndarray,
                                      v_pages: jnp.ndarray,
                                      k_scale: jnp.ndarray,
                                      v_scale: jnp.ndarray,
                                      page_table: jnp.ndarray,
                                      lengths: jnp.ndarray,
                                      k_new: jnp.ndarray,
                                      v_new: jnp.ndarray, *,
                                      pages_per_block: int = 1,
                                      interpret: bool | None = None
                                      ) -> jnp.ndarray:
    """:func:`paged_decode_attention_grouped` over int8 pages.

    k/v_pages hold int8 codes; k/v_scale ``[P, ps]`` f32 hold one dequant
    factor per resident token row.  The scales ride the SAME page index
    maps as their pages (one extra [ps] f32 vector per page DMA), and
    dequantization happens in VMEM between the DMA and the QK^T matmul:
    HBM sees only int8.
    """
    assert k_pages.dtype == jnp.int8, k_pages.dtype
    return _paged_call(q4, k_pages, v_pages, (k_scale, v_scale), page_table,
                       lengths, k_new, v_new,
                       pages_per_block=pages_per_block, interpret=interpret)


def paged_decode_attention_q8(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, page_table: jnp.ndarray,
                              lengths: jnp.ndarray, k_new: jnp.ndarray,
                              v_new: jnp.ndarray, *,
                              k_scale: jnp.ndarray, v_scale: jnp.ndarray,
                              pages_per_block: int = 1,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Model layout int8 entry: q [B,1,H,Dh], k/v_new [B,1,KVH,Dh],
    int8 pages + [P, ps] scales -> [B,1,H,Dh]."""
    b, _, h, dh = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    q4 = q.reshape(b, kvh, g, dh)
    out = paged_decode_attention_q8_grouped(
        q4, k_pages, v_pages, k_scale, v_scale, page_table, lengths,
        k_new.reshape(b, kvh, dh), v_new.reshape(b, kvh, dh),
        pages_per_block=pages_per_block, interpret=interpret)
    return out.reshape(b, 1, h, dh)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, *,
                           pages_per_block: int = 1,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Model layout: q [B,1,H,Dh], k_new/v_new [B,1,KVH,Dh] -> [B,1,H,Dh]."""
    b, _, h, dh = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    q4 = q.reshape(b, kvh, g, dh)
    out = paged_decode_attention_grouped(
        q4, k_pages, v_pages, page_table, lengths,
        k_new.reshape(b, kvh, dh), v_new.reshape(b, kvh, dh),
        pages_per_block=pages_per_block, interpret=interpret)
    return out.reshape(b, 1, h, dh)
