"""Paged decode attention (Pallas, TPU-targeted): O(length) bytes/token.

The decode hot path used to score the ENTIRE [B, max_seq, KVH, Dh] cache
buffer every token and mask — bytes/token was O(max_seq) even for rows
holding 30 tokens of context.  This kernel walks each row's *page table*
instead: the KV cache lives in a pool of fixed-size pages
(``serve/kv_pool.py``), each row owns exactly ``ceil(length / page_size)``
of them, and decode touches only those.

Structure (grid = one step per batch row; the kv heads of a page are one
lane-dense ``[ps, KVH*Dh]`` tile):

* the page table ``[B, NP]`` and per-row lengths ``[B]`` are scalar-
  prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``); the pool slice
  stays in HBM (``memory_space=pl.ANY``) and the kernel issues its own
  DMAs;
* a row's pages are walked in blocks of ``pages_per_block`` (ppb) pages:
  one block is ppb page DMAs, each from the physical page ``pt[b, j]``
  into its slot of one ``[ppb, ps, KVH*Dh]`` VMEM buffer (the gather
  happens in the DMA engine, no materialized gathered copy), then one
  QK^T matmul and one P.V matmul over its ``ppb * ps`` keys for every kv
  head at once, in f32;
* the walk is a ``lax.fori_loop`` of ``ceil(length[b] / (ppb * ps))``
  trips read from the prefetched lengths: a row of length 0 costs one
  grid step and no DMA, the pages past a row's length cost nothing, and
  of the last block only the live pages are fetched — its dead key rows
  are masked (scores to -inf, values to 0, so stale VMEM never counts);
  a block's pages are issued in a loop over its live pages, so the
  kernel's code (and its compile time) does not grow with ppb, and a
  full block is retired by one wait per operand;
* the DMAs are double-buffered: block k+1 of the row, or at the row's
  last block the first block of the next non-empty row, is in flight
  while block k is scored; which of the two buffers holds the block in
  flight crosses grid steps in SMEM, so the grid runs in order
  ("arbitrary");
* online softmax state (running max / denominator / accumulator) lives in
  VMEM scratch across the blocks; after the last one the NEW token's K/V
  (one [KVH, Dh] row, passed separately so the caller can scatter it into
  its page afterwards) is folded into the same softmax and the output
  normalized — the exact two-part-softmax contract of
  ``models/attention.py::decode_attention_token``;
* ``pages_per_block`` is the tile knob: ``registry.default_pages_per_block``
  derives it from the page size, and ``kernels/autotune.py`` sweeps it
  with ``page_size``.

Layout contract: q grouped [B, KVH, G, Dh]; pages [P, page_size, KVH, Dh]
(the pool layout, one layer's slice).  ``paged_decode_attention`` adapts
from the model's [B, 1, H, Dh].  Oracle: kernels/ref.py::paged_decode.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_attention_grouped",
           "paged_decode_attention_q8", "paged_decode_attention_q8_grouped"]

NEG_INF = -2.0e38


def _paged_kernel(lens_ref, pt_ref, q_ref, kn_ref, vn_ref, *refs,
                  scale: float, ps: int, ppb: int, kvh: int, dh: int,
                  np_w: int, quantized: bool):
    """refs: the HBM pages k, v [and scales k, v when quantized], then o,
    then scratch: the VMEM block buffers (one per HBM operand, two slots
    each), the DMA semaphores [2, operands], the SMEM slot of the block in
    flight, and m, l, acc.

    Each kv head's queries sit block-diagonally in rows of a
    ``[KVH*Gp, KVH*Dh]`` tile (zeros outside the head's lanes, G padded to
    a multiple of 8), so one full-width matmul scores every head against
    its own keys; the accumulator rows are full width too, and the finish
    keeps each head's own lanes.  No lane slice at a sub-128 offset is
    ever taken.
    """
    n_hbm = 4 if quantized else 2
    hbm = refs[:n_hbm]
    o_ref = refs[n_hbm]
    bufs = refs[n_hbm + 1:2 * n_hbm + 1]
    sems, slot_ref, m_ref, l_ref, acc_ref = refs[2 * n_hbm + 1:]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    bk = ppb * ps
    r, w = acc_ref.shape

    def row_len(row):
        # keys past the table's capacity do not exist (as in the oracle)
        return jnp.minimum(lens_ref[row], np_w * ps)

    def dma(row, blk, slot, op):
        """Issue (op="start") or retire (op="wait") block ``blk`` of
        ``row`` in ``slot``: one copy per live page and HBM operand."""
        live = jnp.minimum(ppb, (row_len(row) + ps - 1) // ps - blk * ppb)

        def page(i, carry):
            p = pt_ref[row, blk * ppb + i]
            for j, (src, buf) in enumerate(zip(hbm, bufs)):
                getattr(pltpu.make_async_copy(src.at[p], buf.at[slot, i],
                                              sems.at[slot, j]), op)()
            return carry

        if op == "wait":
            # a full block is retired by one wait per operand for all of
            # its bytes
            @pl.when(live == ppb)
            def _full_block():
                for j, buf in enumerate(bufs):
                    pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                          sems.at[slot, j]).wait()

            @pl.when(live < ppb)
            def _last_block():
                jax.lax.fori_loop(0, live, page, 0)
        else:
            jax.lax.fori_loop(0, live, page, 0)

    length = row_len(b)
    n_blk = (length + bk - 1) // bk
    nxt = jnp.minimum(b + 1, nb - 1)
    has_next = jnp.logical_and(b + 1 < nb, row_len(nxt) > 0)

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0

        @pl.when(n_blk > 0)
        def _():
            dma(b, 0, 0, "start")

    # from here on the row's first block (if it has one) is in flight in
    # ``slot0``; an empty row hands that slot to the next row's first block
    slot0 = slot_ref[0]

    @pl.when(jnp.logical_and(n_blk == 0, has_next))
    def _prefetch_next_row():
        dma(nxt, 0, slot0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...].astype(jnp.float32) * scale             # [R, W]

    def block(i, carry):
        slot = jax.lax.rem(slot0 + i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            dma(b, i + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(i + 1 == n_blk, has_next))
        def _():
            dma(nxt, 0, 1 - slot, "start")

        dma(b, i, slot, "wait")
        k = bufs[0][slot].astype(jnp.float32)             # [ppb, ps, W]
        v = bufs[1][slot].astype(jnp.float32)
        if quantized:
            # dequant in VMEM: int8 codes x f32 per-token scales (every
            # lane of a scale tile holds the same value)
            k = k * jnp.max(bufs[2][slot], axis=-1, keepdims=True)
            v = v * jnp.max(bufs[3][slot], axis=-1, keepdims=True)
        k = k.reshape(bk, w)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        ok = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < length
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        v = v.reshape(bk, w)
        live = (i * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                < length)
        v = jnp.where(live, v, 0.0)       # the last block's dead rows
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(pr, v)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    slot_ref[0] = jax.lax.rem(slot0 + n_blk, 2)

    # the new token attends itself: fold its single K/V row into the
    # running softmax, then normalize — rows with length == 0 (empty
    # slots) come through here with (m, l, acc) untouched and output
    # exactly softmax over {the token} = v_new
    kt = kn_ref[...].astype(jnp.float32)                   # [1, W]
    vt = vn_ref[...].astype(jnp.float32)                   # [1, W]
    s_t = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())))
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s_t)
    alpha = jnp.exp(m_prev - m_new)
    p_t = jnp.exp(s_t - m_new)
    l = l_ref[...] * alpha + p_t
    acc = acc_ref[...] * alpha + p_t * vt
    res = (acc / jnp.maximum(l, 1e-20)).reshape(kvh, r // kvh, w)
    head = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1) // dh
    out = jnp.zeros(o_ref.shape, jnp.float32)
    for h in range(kvh):
        out = jnp.where(head == h, res[h], out)
    o_ref[...] = out.astype(o_ref.dtype)


def _paged_call(q4, k_pages, v_pages, scales, page_table, lengths, k_new,
                v_new, *, pages_per_block: int, interpret,
                compiler_params=None):
    """Shared pallas_call for the fp and int8 page flavors.

    ``scales`` is None (fp pages) or ``(k_scale, v_scale)`` ``[P, ps]``,
    fetched beside their pages as lane-dense ``[ps, 128]`` tiles.  The pool
    ``[P, ps, KVH, Dh]`` is viewed as ``[P, ps, KVH*Dh]`` (a reshape,
    though not a free one on a v5e): a page is then one lane-dense tile,
    which the TPU tiling accepts for any KVH and Dh — a per-head tile
    would put KVH in the sublane dim, refused unless KVH is a multiple of
    8.  ``compiler_params`` (``pltpu.CompilerParams``) passes through to
    the ``pallas_call``, e.g. to hold the kernel to a VMEM limit.
    """
    from repro.kernels import registry
    if interpret is None:
        interpret = registry.default_interpret()
    b, kvh, g, dh = q4.shape
    p_total, ps, kvh_p, _ = k_pages.shape
    assert kvh_p == kvh, (kvh_p, kvh)
    w = kvh * dh
    np_w = page_table.shape[1]
    ppb = max(1, min(pages_per_block or registry.default_pages_per_block(ps),
                     np_w))
    gp = -(-g // 8) * 8
    lengths = jnp.asarray(lengths, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    # block-diagonal queries: head h's [G, Dh] in lanes [h*Dh, (h+1)*Dh)
    # of rows [h*Gp, h*Gp + G)
    eye = jnp.eye(kvh, dtype=q4.dtype)
    qw = (eye[None, :, None, :, None] * q4[:, :, :, None, :]).reshape(
        b, kvh, g, w)
    qw = jnp.pad(qw, ((0, 0), (0, 0), (0, gp - g), (0, 0))).reshape(
        b, kvh * gp, w)
    kn = k_new.reshape(b, 1, w)
    vn = v_new.reshape(b, 1, w)
    # on a v5e the [ps, KVH, Dh] and [ps, W] tilings differ, so XLA
    # copies the whole pool to make this view: pool movement outside the
    # kernel, named as such
    with jax.named_scope("kv_cache"):
        k_rows = k_pages.reshape(p_total, ps, w)
        v_rows = v_pages.reshape(p_total, ps, w)
    hbm = [k_rows, v_rows]
    bufs = [pltpu.VMEM((2, ppb, ps, w), k_rows.dtype),
            pltpu.VMEM((2, ppb, ps, w), v_rows.dtype)]
    if scales is not None:
        # [P, ps] -> [P, ps, 128]: a page's scales as one lane-dense tile
        # (a [ps, 1] column is stored lane-padded to the same bytes, but
        # its DMA window is refused by the TPU tiling)
        hbm += [jnp.broadcast_to(sc.astype(jnp.float32)[..., None],
                                 (p_total, ps, 128)) for sc in scales]
        bufs += [pltpu.VMEM((2, ppb, ps, 128), jnp.float32)] * 2
    row = lambda b_, lens, pt: (b_, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # lengths, page_table
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, kvh * gp, w), row),
            pl.BlockSpec((None, 1, w), row),
            pl.BlockSpec((None, 1, w), row),
            *[pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        ],
        out_specs=pl.BlockSpec((None, gp, w), row),
        scratch_shapes=[
            *bufs,
            pltpu.SemaphoreType.DMA((2, len(hbm))),
            pltpu.SMEM((1,), jnp.int32),              # slot in flight
            pltpu.VMEM((kvh * gp, 1), jnp.float32),   # running max
            pltpu.VMEM((kvh * gp, 1), jnp.float32),   # denominator
            pltpu.VMEM((kvh * gp, w), jnp.float32),   # output accumulator
        ],
    )
    # a row's last block prefetches the next row's first: rows run in order
    params = (dataclasses.replace(compiler_params,
                                  dimension_semantics=("arbitrary",))
              if compiler_params is not None else
              pltpu.CompilerParams(dimension_semantics=("arbitrary",)))
    kernel = functools.partial(_paged_kernel, scale=1.0 / (dh ** 0.5),
                               ps=ps, ppb=ppb, kvh=kvh, dh=dh, np_w=np_w,
                               quantized=scales is not None)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, gp, w), q4.dtype),
        compiler_params=params,
        interpret=interpret,
    )(lengths, page_table, qw, kn, vn, *hbm)
    # [B, Gp, KVH*Dh] -> [B, KVH, G, Dh]
    return out[:, :g].reshape(b, g, kvh, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention_grouped(q4: jnp.ndarray, k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   page_table: jnp.ndarray,
                                   lengths: jnp.ndarray,
                                   k_new: jnp.ndarray, v_new: jnp.ndarray, *,
                                   pages_per_block: int | None = None,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """q4: [B,KVH,G,Dh]; k/v_pages: [P,ps,KVH,Dh]; page_table: [B,NP] int32;
    lengths: [B] int32 (past tokens; the new token is NOT in the pages yet);
    k_new/v_new: [B,KVH,Dh].  Returns [B,KVH,G,Dh].

    ``page_table[b, j]`` is the physical page holding row b's tokens
    ``[j*ps, (j+1)*ps)``; entries past ``ceil(lengths[b]/ps)`` are never
    read (no DMA, no loop trip), so rows with ``lengths[b] == 0`` read no
    page at all.  ``pages_per_block`` defaults to
    ``registry.default_pages_per_block(ps)``.
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
                                      pages_per_block: int | None = None,
                                      interpret: bool | None = None
                                      ) -> jnp.ndarray:
    """:func:`paged_decode_attention_grouped` over int8 pages.

    k/v_pages hold int8 codes; k/v_scale ``[P, ps]`` f32 hold one dequant
    factor per resident token row.  The scales ride the SAME block DMAs
    as their pages (one extra [ps, 128] f32 tile per page), and
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
                              pages_per_block: int | None = None,
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
                           pages_per_block: int | None = None,
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
