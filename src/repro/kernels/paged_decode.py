"""Paged decode attention (Pallas, TPU-targeted): O(length) bytes/token.

The decode hot path used to score the ENTIRE [B, max_seq, KVH, Dh] cache
buffer every token and mask — bytes/token was O(max_seq) even for rows
holding 30 tokens of context.  This kernel walks each row's *page table*
instead: the KV cache lives in a pool of fixed-size pages
(``serve/kv_pool.py``), each row owns exactly ``ceil(length / page_size)``
of them, and decode touches only those.

Structure (grid = one step per batch row; the kv heads of a page are one
lane-dense ``[ps, KVH*Dh]`` tile):

* the page table ``[B, NP]``, the per-row lengths ``[B]`` and the layer
  index are scalar-prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``);
  the STACKED pool ``[L, P, ps, KVH*Dh]`` stays in HBM
  (``memory_space=pl.ANY``) and the kernel issues its own DMAs, each
  reading page ``pt[b, j]`` of the prefetched layer in place — the
  decode program's layer scan hands the kernel its pool carry as is, so
  no layer's pool is ever sliced, relaid or copied outside the kernel;
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
* int8 pages: the wrapper gathers the scales of the pages each row's
  table lists (``[B, NP*ps]`` f32, the table's size, never the pool's)
  and hands them in as one lane-dense row per block; the kernel scales
  the block's scores by the K scales and its probabilities by the V
  scales, so the int8 codes go straight into the matmuls;
* online softmax state (running max / denominator / accumulator) lives in
  VMEM scratch across the blocks; after the last one the NEW token's K/V
  (one [KVH, Dh] row, passed separately so the caller can scatter it into
  its page afterwards) is folded into the same softmax and the output
  normalized — the exact two-part-softmax contract of
  ``models/attention.py::decode_attention_token``;
* ``pages_per_block`` is the tile knob: ``registry.default_pages_per_block``
  derives it from the page size, and ``kernels/autotune.py`` sweeps it
  with ``page_size``.

Layout contract: q grouped [B, KVH, G, Dh]; pages [L, P, page_size,
KVH*Dh], the pool as ``PagedKVCache`` stores it (head h in lanes
``[h*Dh, (h+1)*Dh)``), read at ``layer`` (a single-layer pool is
``L = 1``, layer 0); int8 scales [L, P, page_size].
``paged_decode_attention`` adapts from the model's [B, 1, H, Dh].
Oracle: kernels/ref.py::paged_decode.
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


def _paged_kernel(lens_ref, pt_ref, layer_ref, q_ref, kn_ref, vn_ref, *refs,
                  scale: float, ps: int, ppb: int, kvh: int, dh: int,
                  np_w: int, quantized: bool):
    """refs: [the per-block scale rows k, v when quantized], the stacked
    HBM pages k, v, then o, then scratch: the VMEM block buffers (one per
    HBM operand, two slots each), the DMA semaphores [2, 2], the SMEM slot
    of the block in flight, and m, l, acc.

    Each kv head's queries sit block-diagonally in rows of a
    ``[KVH*Gp, KVH*Dh]`` tile (zeros outside the head's lanes, G padded to
    a multiple of 8), so one full-width matmul scores every head against
    its own keys; the accumulator rows are full width too, and the finish
    keeps each head's own lanes.  No lane slice at a sub-128 offset is
    ever taken.
    """
    n_sc = 2 if quantized else 0
    scales = refs[:n_sc]
    hbm = refs[n_sc:n_sc + 2]
    o_ref = refs[n_sc + 2]
    bufs = refs[n_sc + 3:n_sc + 5]
    sems, slot_ref, m_ref, l_ref, acc_ref = refs[n_sc + 5:]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    bk = ppb * ps
    r, w = acc_ref.shape

    def row_len(row):
        # keys past the table's capacity do not exist (as in the oracle)
        return jnp.minimum(lens_ref[row], np_w * ps)

    def dma(row, blk, slot, op):
        """Issue (op="start") or retire (op="wait") block ``blk`` of
        ``row`` in ``slot``: one copy per live page and HBM operand, read
        in place from the layer's pages of the stacked pool."""
        live = jnp.minimum(ppb, (row_len(row) + ps - 1) // ps - blk * ppb)

        def page(i, carry):
            p = pt_ref[row, blk * ppb + i]
            for j, (src, buf) in enumerate(zip(hbm, bufs)):
                getattr(pltpu.make_async_copy(src.at[layer, p],
                                              buf.at[slot, i],
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
        k = bufs[0][slot].astype(jnp.float32).reshape(bk, w)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        ok = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < length
        if quantized:
            # int8 codes: a key's dequant factor scales its score column
            s = s * scales[0][pl.ds(i, 1), :]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        if quantized:
            # ... and a value's its probability column (a dead key's
            # scale, whatever it holds, is masked out)
            pr = pr * jnp.where(ok, scales[1][pl.ds(i, 1), :], 0.0)
        v = bufs[1][slot].astype(jnp.float32).reshape(bk, w)
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


def _block_scales(scales, page_table, layer, ppb: int, n_blk: int):
    """The scales of the pages each row's table lists, one lane-dense row
    of ``ppb * ps`` keys per block: ``[B, n_blk, ppb * ps]`` f32, gathered
    straight from the stacked ``[L, P, ps]`` scale pool at ``layer`` (the
    table's width past NP reads the null page, never a live key)."""
    b, np_w = page_table.shape
    pt = jnp.pad(page_table, ((0, 0), (0, n_blk * ppb - np_w)))
    return tuple(sc[layer, pt].astype(jnp.float32).reshape(b, n_blk, -1)
                 for sc in scales)


def _paged_call(q4, k_pages, v_pages, scales, page_table, lengths, k_new,
                v_new, layer=0, *, pages_per_block: int, interpret,
                compiler_params=None):
    """Shared pallas_call for the fp and int8 page flavors.

    ``k_pages``/``v_pages`` are the stacked pool ``[L, P, ps, KVH*Dh]``
    and ``layer`` the (traced) layer the kernel reads: a page is one
    lane-dense tile, which the TPU tiling accepts for any KVH and Dh (a
    per-head tile would put KVH in the sublane dim, refused unless KVH is
    a multiple of 8), and the kernel's DMAs read it in place.  ``scales``
    is None (fp pages) or ``(k_scale, v_scale)`` ``[L, P, ps]``.
    ``compiler_params`` (``pltpu.CompilerParams``) passes through to the
    ``pallas_call``, e.g. to hold the kernel to a VMEM limit.
    """
    from repro.kernels import registry
    if interpret is None:
        interpret = registry.default_interpret()
    b, kvh, g, dh = q4.shape
    _, _, ps, w = k_pages.shape
    assert w == kvh * dh, (k_pages.shape, q4.shape)
    np_w = page_table.shape[1]
    ppb = max(1, min(pages_per_block or registry.default_pages_per_block(ps),
                     np_w))
    gp = -(-g // 8) * 8
    lengths = jnp.asarray(lengths, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # block-diagonal queries: head h's [G, Dh] in lanes [h*Dh, (h+1)*Dh)
    # of rows [h*Gp, h*Gp + G)
    eye = jnp.eye(kvh, dtype=q4.dtype)
    qw = (eye[None, :, None, :, None] * q4[:, :, :, None, :]).reshape(
        b, kvh, g, w)
    qw = jnp.pad(qw, ((0, 0), (0, 0), (0, gp - g), (0, 0))).reshape(
        b, kvh * gp, w)
    kn = k_new.reshape(b, 1, w)
    vn = v_new.reshape(b, 1, w)
    row = lambda b_, lens, pt, layer: (b_, 0, 0)
    blocked = [qw, kn, vn]
    in_specs = [pl.BlockSpec((None, kvh * gp, w), row),
                pl.BlockSpec((None, 1, w), row),
                pl.BlockSpec((None, 1, w), row)]
    if scales is not None:
        n_blk = -(-np_w // ppb)
        blocked += _block_scales(scales, page_table, layer[0], ppb, n_blk)
        in_specs += [pl.BlockSpec((None, n_blk, ppb * ps), row)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # lengths, page_table, layer
        grid=(b,),
        in_specs=[*in_specs, *[pl.BlockSpec(memory_space=pl.ANY)] * 2],
        out_specs=pl.BlockSpec((None, gp, w), row),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, w), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, w), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
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
    )(lengths, page_table, layer, *blocked, k_pages, v_pages)
    # [B, Gp, KVH*Dh] -> [B, KVH, G, Dh]
    return out[:, :g].reshape(b, g, kvh, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention_grouped(q4: jnp.ndarray, k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   page_table: jnp.ndarray,
                                   lengths: jnp.ndarray,
                                   k_new: jnp.ndarray, v_new: jnp.ndarray,
                                   layer=0, *,
                                   pages_per_block: int | None = None,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """q4: [B,KVH,G,Dh]; k/v_pages: [L,P,ps,KVH*Dh] read at ``layer``;
    page_table: [B,NP] int32; lengths: [B] int32 (past tokens; the new
    token is NOT in the pages yet); k_new/v_new: [B,KVH,Dh].  Returns
    [B,KVH,G,Dh].

    ``page_table[b, j]`` is the physical page holding row b's tokens
    ``[j*ps, (j+1)*ps)``; entries past ``ceil(lengths[b]/ps)`` are never
    read (no DMA, no loop trip), so rows with ``lengths[b] == 0`` read no
    page at all.  ``pages_per_block`` defaults to
    ``registry.default_pages_per_block(ps)``.
    """
    return _paged_call(q4, k_pages, v_pages, None, page_table, lengths,
                       k_new, v_new, layer, pages_per_block=pages_per_block,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention_q8_grouped(q4: jnp.ndarray, k_pages: jnp.ndarray,
                                      v_pages: jnp.ndarray,
                                      k_scale: jnp.ndarray,
                                      v_scale: jnp.ndarray,
                                      page_table: jnp.ndarray,
                                      lengths: jnp.ndarray,
                                      k_new: jnp.ndarray,
                                      v_new: jnp.ndarray, layer=0, *,
                                      pages_per_block: int | None = None,
                                      interpret: bool | None = None
                                      ) -> jnp.ndarray:
    """:func:`paged_decode_attention_grouped` over int8 pages.

    k/v_pages hold int8 codes; k/v_scale ``[L, P, ps]`` f32 hold one
    dequant factor per resident token row.  Only the scales of the pages
    the table lists are gathered (one lane-dense row per block of each
    row's walk), and they apply to the block's scores and probabilities
    in VMEM: HBM sees only int8 pages.
    """
    assert k_pages.dtype == jnp.int8, k_pages.dtype
    return _paged_call(q4, k_pages, v_pages, (k_scale, v_scale), page_table,
                       lengths, k_new, v_new, layer,
                       pages_per_block=pages_per_block, interpret=interpret)


def paged_decode_attention_q8(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, page_table: jnp.ndarray,
                              lengths: jnp.ndarray, k_new: jnp.ndarray,
                              v_new: jnp.ndarray, *,
                              k_scale: jnp.ndarray, v_scale: jnp.ndarray,
                              layer=0, pages_per_block: int | None = None,
                              interpret: bool | None = None) -> jnp.ndarray:
    """Model layout int8 entry: q [B,1,H,Dh], k/v_new [B,1,KVH,Dh],
    int8 pages [L,P,ps,KVH*Dh] + [L,P,ps] scales -> [B,1,H,Dh]."""
    b, _, h, dh = q.shape
    kvh = k_new.shape[2]
    q4 = q.reshape(b, kvh, h // kvh, dh)
    out = paged_decode_attention_q8_grouped(
        q4, k_pages, v_pages, k_scale, v_scale, page_table, lengths,
        k_new.reshape(b, kvh, dh), v_new.reshape(b, kvh, dh), layer,
        pages_per_block=pages_per_block, interpret=interpret)
    return out.reshape(b, 1, h, dh)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, *, layer=0,
                           pages_per_block: int | None = None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Model layout: q [B,1,H,Dh], pages [L,P,ps,KVH*Dh] read at
    ``layer``, k_new/v_new [B,1,KVH,Dh] -> [B,1,H,Dh]."""
    b, _, h, dh = q.shape
    kvh = k_new.shape[2]
    q4 = q.reshape(b, kvh, h // kvh, dh)
    out = paged_decode_attention_grouped(
        q4, k_pages, v_pages, page_table, lengths,
        k_new.reshape(b, kvh, dh), v_new.reshape(b, kvh, dh), layer,
        pages_per_block=pages_per_block, interpret=interpret)
    return out.reshape(b, 1, h, dh)
