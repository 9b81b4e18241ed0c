"""GQA attention: init, train/prefill forward (full or Q-chunked), decode.

Three execution paths, chosen by config (all numerically equivalent; the
chunked path is the memory-safe default above ``chunk_threshold`` tokens and
doubles as the pure-jnp oracle for the Pallas flash kernel):

* ``full``     — materializes [B,H,Sq,Sk] scores (small sequences only).
* ``chunked``  — lax.scan over query chunks; [B,H,C,Sk] live at once.
* ``decode``   — one new token against a KV cache; supports caches whose
                 sequence dim is sharded (softmax reductions over the
                 sharded axis become small all-reduces under SPMD).

GQA grouping: q heads H = KVH * G.  KV caches are stored [B, S, KVH, Dh].
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (Params, Specs, apply_mrope, apply_rope,
                                 dense_init, rms_norm, truncated_normal_init)

__all__ = ["AttnConfig", "init_attn", "attn_specs", "attention",
           "KVCache", "init_kv_cache", "decode_attention",
           "prefill_into_cache", "PagedKVCache", "init_paged_kv_cache",
           "prefill_into_paged_cache", "paged_decode_attention_token",
           "paged_decode_jnp", "quantize_kv_rows", "dequantize_gathered"]

NEG_INF = -2.0e38


class AttnConfig(NamedTuple):
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    # Qwen3: an RMSNorm over head_dim, with learned scales, on every query
    # and key head after the projection and before RoPE (so before K is
    # cached)
    qk_norm: bool = False
    norm_eps: float = 1e-6
    causal: bool = True
    use_rope: bool = True
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # Qwen2-VL
    chunk_size: int = 512
    chunk_threshold: int = 2048   # use chunked path above this many q tokens
    # softmax_mode: "naive" = textbook mask->softmax(f32)->cast (the paper-
    # faithful baseline); "fused" = scale folded into q, mask folded into the
    # reductions, probs stored in compute dtype, 1/denom applied to the PV
    # output — ~2.3x less HBM traffic over the [B,H,Sq,Sk] tensors
    # (EXPERIMENTS.md §Perf hillclimb 1)
    softmax_mode: str = "naive"


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attn(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    std = 1.0 / np.sqrt(d)
    p = {
        "wq": truncated_normal_init(kq, (d, h, dh), dtype, std),
        "wk": truncated_normal_init(kk, (d, kvh, dh), dtype, std),
        "wv": truncated_normal_init(kv, (d, kvh, dh), dtype, std),
        "wo": truncated_normal_init(ko, (h, dh, d), dtype, 1.0 / np.sqrt(h * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kvh, dh), dtype)
        p["bv"] = jnp.zeros((kvh, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((dh,), jnp.float32)}
    return p


def attn_specs(cfg: AttnConfig) -> Specs:
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        s["bq"] = ("heads", "head_dim")
        s["bk"] = ("kv_heads", "head_dim")
        s["bv"] = ("kv_heads", "head_dim")
    if cfg.qk_norm:
        s["q_norm"] = {"scale": ("head_dim",)}
        s["k_norm"] = {"scale": ("head_dim",)}
    return s


# ---------------------------------------------------------------------------
# projections + rope
# ---------------------------------------------------------------------------

def _project_qkv(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                 positions: jnp.ndarray,
                 positions3: Optional[jnp.ndarray] = None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if not cfg.use_rope:
        return q, k, v
    if cfg.mrope_sections is not None and positions3 is not None:
        q = apply_mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q: [B,Sq,H,Dh], k: [B,Sk,KVH,Dh] -> scores [B,KVH,G,Sq,Sk]."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / np.sqrt(dh)


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """probs: [B,KVH,G,Sq,Sk], v: [B,Sk,KVH,Dh] -> [B,Sq,H,Dh]."""
    b, kvh, g, sq, _ = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, kvh * g, v.shape[-1])


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _full_attention(q, k, v, q_offset: int = 0, causal: bool = True,
                    softmax_mode: str = "naive",
                    kv_len=None) -> jnp.ndarray:
    return _full_attention_offset(q, k, v, q_offset, causal, softmax_mode,
                                  kv_len=kv_len)


def _chunked_attention(q, k, v, chunk: int, causal: bool = True,
                       softmax_mode: str = "naive",
                       kv_len=None) -> jnp.ndarray:
    """Q-chunked causal attention: scan over query chunks, full K/V.

    Live intermediates are [B,KVH,G,chunk,Sk] — the 32k-prefill-safe path.
    """
    b, sq, h, dh = q.shape
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // chunk
    qs = q.reshape(b, nq, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, args):
        i, qc = args
        out = _full_attention_offset(qc, k, v, i * chunk, causal,
                                     softmax_mode, kv_len=kv_len)
        return carry, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, dh)
    return out[:, :sq]


def _kv_len_mask(kv_len, sk: int) -> jnp.ndarray:
    """Per-row key-validity mask [B,1,1,1,Sk]: key j is real iff j < len_b."""
    return (jnp.arange(sk)[None, :] < kv_len[:, None])[:, None, None, None, :]


def _full_attention_offset(qc, k, v, q_offset, causal: bool = True,
                           softmax_mode: str = "naive",
                           kv_len=None) -> jnp.ndarray:
    if softmax_mode == "fused":
        return _fused_attention_offset(qc, k, v, q_offset, causal, kv_len)
    if softmax_mode == "kernel":
        # the registry decides which kernel family runs; the grad-safe
        # flash twin is the default (the Pallas kernel is forward-only),
        # env/context overrides force a specific impl
        from repro.kernels import registry
        impl = registry.select(
            "attention", sq=qc.shape[1], sk=k.shape[1], dh=qc.shape[-1],
            causal=causal, differentiable=True)
        return registry.run("attention", qc, k, v, impl=impl,
                            q_offset=q_offset, causal=causal, kv_len=kv_len)
    sq, sk = qc.shape[1], k.shape[1]
    scores = _gqa_scores(qc, k).astype(jnp.float32)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_len is not None:
        scores = jnp.where(_kv_len_mask(kv_len, sk), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
    return _gqa_out(probs, v)


def _fused_attention_offset(qc, k, v, q_offset, causal: bool = True,
                            kv_len=None) -> jnp.ndarray:
    """Traffic-lean attention (§Perf hillclimb 1).

    Same math as the naive path, restructured so XLA materializes the
    [B,KVH,G,Sq,Sk] tensor family 2.3x cheaper:

    * 1/sqrt(dh) multiplies q ([B,S,H,dh]) instead of the scores (S^2);
    * the causal mask is folded into the max/exp *reductions* (fuses into
      their input) instead of a standalone select pass;
    * un-normalized probs are stored in compute dtype (bf16 in prod);
    * the 1/denominator lands on the PV output ([...,Sq,dh], 1/64th the
      bytes of the probs tensor).

    f32 is kept where accumulation accuracy lives: the QK^T accumulator,
    the running max, and the denominator sum.
    """
    b, sq, h, dh = qc.shape
    sk = k.shape[1]
    qs = qc * jnp.asarray(1.0 / np.sqrt(dh), qc.dtype)
    kvh = k.shape[2]
    g = h // kvh
    qg = qs.reshape(b, sq, kvh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    if causal:
        # ADDITIVE mask: the add input-fuses into both reductions below, so
        # no masked-scores tensor is ever materialized (a select/where is
        # materialized once per consumer — 2 extra S^2 passes)
        qpos = jnp.arange(sq) + q_offset
        bias = jnp.where(
            (jnp.arange(sk)[None, :] <= qpos[:, None]),
            0.0, NEG_INF).astype(jnp.float32)[None, None, None]
        masked = scores + bias
    else:
        masked = scores
    if kv_len is not None:
        masked = masked + jnp.where(_kv_len_mask(kv_len, sk),
                                    0.0, NEG_INF).astype(jnp.float32)
    m = jax.lax.stop_gradient(
        jnp.max(masked, axis=-1, keepdims=True))          # f32 [.,Sq,1]
    p = jnp.exp(masked - m).astype(qc.dtype)              # stored compute-dtype
    denom = jnp.sum(p.astype(jnp.float32), axis=-1)       # f32 [.,Sq]
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                     preferred_element_type=jnp.float32)
    denom_q = jnp.moveaxis(denom, 3, 1)                   # -> [b,Sq,kvh,g]
    out = out / jnp.maximum(denom_q, 1e-37)[..., None]
    return out.astype(qc.dtype).reshape(b, sq, h, v.shape[-1])


def _tile_bias(qpos, kpos, causal: bool, kv_len) -> jnp.ndarray:
    """Additive tile bias [B,1,1,sq|1,bk]: per-row KV validity (ragged /
    padded keys) folded together with the causal offset mask."""
    ok = (kpos[None, :] < kv_len[:, None])[:, None, None, None, :]
    if causal:
        ok = ok & (kpos[None, :] <= qpos[:, None])[None, None, None]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_core(qs, k, v, qpos, kv_len, causal: bool, k_chunk: int):
    out, _ = _flash_fwd_loop(qs, k, v, qpos, kv_len, causal, k_chunk)
    return out


def _flash_fwd_loop(qs, k, v, qpos, kv_len, causal, k_chunk):
    """Online-softmax forward: returns (out [b,kvh,g,sq,dh], L [.,sq])."""
    b, sq, kvh, g, dh = qs.shape
    nk = k.shape[1] // k_chunk
    with jax.named_scope("vmem_kernel_flash_fwd"):
        kt = k.reshape(b, nk, k_chunk, kvh, dh).transpose(1, 0, 2, 3, 4)
        vt = v.reshape(b, nk, k_chunk, kvh, dh).transpose(1, 0, 2, 3, 4)

        def body(carry, args):
            acc, m, l = carry
            i, kc, vc = args
            s = jnp.einsum("bqkgd,bskd->bkgqs", qs, kc,
                           preferred_element_type=jnp.float32)
            s = s + _tile_bias(qpos, i * k_chunk + jnp.arange(k_chunk),
                               causal, kv_len)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            # fully-masked rows (kv_len == 0) carry m_new == NEG_INF and
            # p == 1 everywhere; zero them so such rows output 0 exactly
            # (matches the Pallas kernel), instead of a mean over v
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(qs.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, kvh, g, sq, dh), jnp.float32)
        m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0), (jnp.arange(nk), kt, vt))
        l_safe = jnp.maximum(l, 1e-37)
        out = (acc / l_safe[..., None]).astype(qs.dtype)
        lse = m + jnp.log(l_safe)                  # logsumexp residual
    return out, lse


def _flash_fwd(qs, k, v, qpos, kv_len, causal, k_chunk):
    out, lse = _flash_fwd_loop(qs, k, v, qpos, kv_len, causal, k_chunk)
    return out, (qs, k, v, qpos, kv_len, out, lse)


def _flash_bwd(causal, k_chunk, res, dout):
    """Flash backward: per-tile recompute of p = exp(s - lse); never saves
    the [.,Sq,Sk] tensors (exactly what the Pallas bwd kernel does).

    Layouts: out/dout are [b,kvh,g,sq,dh]; qs is [b,sq,kvh,g,dh]."""
    qs, k, v, qpos, kv_len, out, lse = res
    b, sq, kvh, g, dh = qs.shape
    nk = k.shape[1] // k_chunk
    with jax.named_scope("vmem_kernel_flash_bwd"):
        kt = k.reshape(b, nk, k_chunk, kvh, dh).transpose(1, 0, 2, 3, 4)
        vt = v.reshape(b, nk, k_chunk, kvh, dh).transpose(1, 0, 2, 3, 4)
        dout32 = dout.astype(jnp.float32)
        out32 = out.astype(jnp.float32)
        # D = sum_d dout*out  [b,kvh,g,sq]  (the softmax-jvp row term)
        d_row = jnp.einsum("bkgqd,bkgqd->bkgq", dout32, out32)

        def body(dq_acc, args):
            i, kc, vc = args
            s = jnp.einsum("bqkgd,bskd->bkgqs", qs, kc,
                           preferred_element_type=jnp.float32)
            s = s + _tile_bias(qpos, i * k_chunk + jnp.arange(k_chunk),
                               causal, kv_len)
            p = jnp.exp(s - lse[..., None])                  # normalized
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
            dp = jnp.einsum("bkgqd,bskd->bkgqs", dout32, vc)
            dv_c = jnp.einsum("bkgqs,bkgqd->bskd", p, dout32)
            ds = p * (dp - d_row[..., None])
            dq_c = jnp.einsum("bkgqs,bskd->bqkgd", ds, kc)
            dk_c = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                              qs.astype(jnp.float32))
            return dq_acc + dq_c, (dk_c, dv_c)

        dq0 = jnp.zeros((b, sq, kvh, g, dh), jnp.float32)
        dq, (dk_t, dv_t) = jax.lax.scan(
            body, dq0, (jnp.arange(nk), kt, vt))
        dk = dk_t.transpose(1, 0, 2, 3, 4).reshape(b, nk * k_chunk, kvh, dh)
        dv = dv_t.transpose(1, 0, 2, 3, 4).reshape(b, nk * k_chunk, kvh, dh)
    return (dq.astype(qs.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _flash_attention_offset(qc, k, v, q_offset, causal: bool = True,
                            k_chunk: int = 1024, kv_len=None) -> jnp.ndarray:
    """Flash attention for one q-chunk (§Perf hillclimb 1, iteration 3).

    The k/v loops run under the ``vmem_kernel`` scope: on TPU these loops
    ARE kernels/flash_attention.py (pallas_call, tiles resident in VMEM;
    the model zoo swaps it in via ``use_kernel_fn``); the jnp form here is
    its oracle twin, with a custom_vjp whose backward recomputes p per tile
    (the flash-bwd contract — scan autodiff would otherwise save the full
    [.,Sq,Sk] stack).  The scope marker lets the roofline byte model charge
    the loops' *external* traffic (q,k,v in, out/grads out) instead of
    per-iteration HBM round-trips; FLOPs remain counted per-iteration.
    """
    b, sq, h, dh = qc.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    k_chunk = min(k_chunk, max(sk, 128))
    pad = (-sk) % k_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = (qc * jnp.asarray(1.0 / np.sqrt(dh), qc.dtype)
          ).reshape(b, sq, kvh, g, dh)
    qpos = jnp.arange(sq) + q_offset
    # per-row valid KV length; defaults to sk, which also masks the chunk
    # padding rows above (kpos >= sk) — ragged kv_len just tightens it
    kv_len = (jnp.full((b,), sk, jnp.int32) if kv_len is None
              else jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,)))
    out = _flash_core(qs, k, v, qpos, kv_len, causal, k_chunk)
    # [b,kvh,g,sq,dh] -> [b,sq,h,dh]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dh)


def attention(p: Params, x: jnp.ndarray, cfg: AttnConfig, *,
              positions: Optional[jnp.ndarray] = None,
              positions3: Optional[jnp.ndarray] = None,
              use_kernel_fn=None) -> jnp.ndarray:
    """Causal self-attention over x [B,S,D] -> [B,S,D]."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    if use_kernel_fn is not None:
        out = use_kernel_fn(q, k, v)
    elif s > cfg.chunk_threshold:
        out = _chunked_attention(q, k, v, cfg.chunk_size, cfg.causal,
                                 cfg.softmax_mode)
    else:
        out = _full_attention(q, k, v, causal=cfg.causal,
                              softmax_mode=cfg.softmax_mode)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jnp.ndarray          # [B, Smax, KVH, Dh]
    v: jnp.ndarray          # [B, Smax, KVH, Dh]
    length: jnp.ndarray     # [B] int32 — tokens filled so far, per row


def init_kv_cache(batch: int, max_seq: int, cfg: AttnConfig,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((batch,), jnp.int32))


def cache_specs() -> Specs:
    return {"k": ("batch", "cache_seq", "kv_heads", "head_dim"),
            "v": ("batch", "cache_seq", "kv_heads", "head_dim"),
            "length": ("batch",)}


def _row_lengths(length: jnp.ndarray, batch: int) -> jnp.ndarray:
    """Normalize a cache length to per-row [B] (scalar caches broadcast)."""
    length = jnp.asarray(length, jnp.int32)
    if length.ndim == 0:
        return jnp.broadcast_to(length, (batch,))
    return length


def _prefill_qkv_attend(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                        positions3: Optional[jnp.ndarray] = None,
                        lengths: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The cache-agnostic half of prefill: project q/k/v and run the
    dispatched prefill attention.  Returns (attn out [B,S,H,Dh], k, v) —
    the dense and paged prefill paths differ only in where k/v land."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    from repro.kernels import registry
    impl = registry.select(
        "attention", sq=s, sk=s, dh=q.shape[-1], causal=cfg.causal,
        flash_min_seq=cfg.chunk_threshold)
    if impl == "pallas_flash":
        # the kernel blocks internally — no outer q-chunking needed
        out = registry.run("attention", q, k, v, impl=impl, q_offset=0,
                           causal=cfg.causal, kv_len=lengths)
    else:
        # jnp family: keep the q-chunked memory guard above the threshold
        # (the flash twin runs per chunk via softmax_mode="kernel"); "full"
        # stays on the configured paper-faithful softmax_mode
        mode = "kernel" if impl == "jnp_flash" else cfg.softmax_mode
        out = (_chunked_attention(q, k, v, cfg.chunk_size, cfg.causal,
                                  softmax_mode=mode, kv_len=lengths)
               if s > cfg.chunk_threshold
               else _full_attention(q, k, v, causal=cfg.causal,
                                    softmax_mode=mode, kv_len=lengths))
    return out, k, v


def prefill_into_cache(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                       cache: KVCache,
                       positions3: Optional[jnp.ndarray] = None,
                       lengths: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, KVCache]:
    """Run prefill attention AND populate the cache with this segment's K/V.

    ``lengths`` [B] marks the real (unpadded) prompt length per row: keys at
    positions >= lengths[b] are masked out of every query's softmax, so
    right-padded ragged prompts attend only their own tokens.  The cache
    rows record their true lengths — decode continues each row at its own
    position.

    The attention itself goes through the kernel registry
    (:mod:`repro.kernels.registry`): on TPU the Pallas flash kernel IS the
    prefill path (ragged lengths masked in-kernel via ``kv_valid``); on
    interpret-mode hosts the jnp family runs, and the override ladder
    (``use_impl`` / ``REPRO_IMPL`` / legacy ``REPRO_ATTN_IMPL``) forces a
    specific impl either way.
    """
    b, s, _ = x.shape
    out, k, v = _prefill_qkv_attend(p, x, cfg, positions3, lengths)
    newk = jax.lax.dynamic_update_slice(
        cache.k, k.astype(cache.k.dtype), (0, 0, 0, 0))
    newv = jax.lax.dynamic_update_slice(
        cache.v, v.astype(cache.v.dtype), (0, 0, 0, 0))
    new_len = (_row_lengths(lengths, b) if lengths is not None
               else jnp.full((b,), s, jnp.int32))
    new_cache = KVCache(k=newk, v=newv, length=new_len)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def _decode_token_attend(q: jnp.ndarray, k_ctx: jnp.ndarray,
                         v_ctx: jnp.ndarray, valid: jnp.ndarray,
                         k_tok: jnp.ndarray, v_tok: jnp.ndarray
                         ) -> jnp.ndarray:
    """Two-part softmax over (masked context, the new token itself).

    q [B,1,H,Dh]; k/v_ctx [B,S,KVH,Dh]; valid [B,S] (which context keys
    are real); k/v_tok [B,1,KVH,Dh].  Returns [B,1,H,Dh].  Shared by the
    dense decode path and the gather-based paged reference so both run
    the IDENTICAL op sequence.
    """
    b = q.shape[0]
    s_c = _gqa_scores(q, k_ctx.astype(q.dtype)).astype(jnp.float32)
    s_c = jnp.where(valid[:, None, None, None, :], s_c, NEG_INF)
    s_t = _gqa_scores(q, k_tok.astype(q.dtype)).astype(jnp.float32)  # [.,1,1]
    m = jnp.maximum(jnp.max(s_c, -1, keepdims=True), s_t)
    p_c = jnp.exp(s_c - m)
    p_t = jnp.exp(s_t - m)
    denom = jnp.sum(p_c, -1, keepdims=True) + p_t
    out_c = _gqa_out((p_c / denom).astype(q.dtype),
                     v_ctx.astype(q.dtype))            # [b,1,h,dh]
    w_t = (p_t / denom).astype(q.dtype)                # [b,kvh,g,1,1]
    # token contribution: broadcast v [b,1,kvh,dh] over the g groups
    vt = v_tok.astype(q.dtype).transpose(0, 2, 1, 3)[:, :, None, :, :]
    out_t = w_t * vt                                   # [b,kvh,g,1,dh]
    kvh, g = w_t.shape[1], w_t.shape[2]
    out_t = out_t.transpose(0, 3, 1, 2, 4).reshape(b, 1, kvh * g, -1)
    return out_c + out_t


def decode_attention_token(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                           k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                           length: jnp.ndarray,
                           positions3: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode against a READ-ONLY cache slice (§Perf hillclimb 3).

    Unlike :func:`decode_attention` this never materializes an updated
    [B,S,KVH,Dh] cache: the new token's K/V are returned for the caller to
    dynamic-update-slice into its (scan-carried, in-place-aliased) stacked
    cache, and attention runs as a two-part softmax over (cache, new token)
    — the 67 MB-per-layer cache rewrite a stacked-ys decode pays becomes a
    16 KB token write.
    """
    b = x.shape[0]
    length = _row_lengths(length, b)                  # [B] per-row positions
    positions = length[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    smax = k_cache.shape[1]
    valid = jnp.arange(smax)[None, :] < length[:, None]   # strictly the past
    out = _decode_token_attend(q, k_cache, v_cache, valid, k, v)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, k, v


def decode_attention(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                     cache: KVCache,
                     positions3: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: x [B,1,D], cache row b holds `length[b]` past tokens.

    The new token's K/V are scatter-written at each row's own index
    `length[b]` (rows advance independently — continuous batching);
    attention spans the whole cache buffer with positions > length[b]
    masked out per row (so a sequence-sharded cache needs no gather —
    masking + all-reduce softmax).
    """
    b = x.shape[0]
    length = _row_lengths(cache.length, b)
    positions = length[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    rows = jnp.arange(b)
    newk = cache.k.at[rows, length].set(k[:, 0].astype(cache.k.dtype))
    newv = cache.v.at[rows, length].set(v[:, 0].astype(cache.v.dtype))

    scores = _gqa_scores(q, newk.astype(q.dtype)).astype(jnp.float32)
    smax = newk.shape[1]
    valid = (jnp.arange(smax)[None, :]
             <= length[:, None])                      # includes the new token
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = _gqa_out(probs, newv.astype(q.dtype))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, KVCache(k=newk, v=newv, length=length + 1)


# ---------------------------------------------------------------------------
# paged KV cache + decode (serve/kv_pool.py storage)
# ---------------------------------------------------------------------------

class PagedKVCache(NamedTuple):
    """Block/page KV storage: rows own ``ceil(length/page_size)`` pages.

    ``k_pages``/``v_pages`` are the POOL — pages are not per-row, the
    page table maps row b's logical page j to physical page
    ``page_table[b, j]``.  Physical page 0 is the null page: unallocated
    table entries point at it, and writes routed there are trash by
    convention (never read — attention masks by ``length``).

    int8 storage: when ``k_scale``/``v_scale`` are present the pages hold
    int8 codes and the scales hold one f32 dequant factor per TOKEN ROW
    (``[P, page_size]``, amax over that token's [KVH, Dh] block / 127).
    Per-row scales mean appends never requantize resident tokens, and the
    paged-decode kernel dequantizes right after the page DMA — HBM
    traffic and pool bytes drop ~4x vs fp32 (2x vs bf16) for the same
    token capacity.
    """

    k_pages: jnp.ndarray     # [P, page_size, KVH, Dh] (fp, or int8 codes)
    v_pages: jnp.ndarray     # [P, page_size, KVH, Dh]
    page_table: jnp.ndarray  # [B, NP] int32 physical page ids
    length: jnp.ndarray      # [B] int32 — tokens filled so far, per row
    k_scale: Optional[jnp.ndarray] = None   # [P, page_size] f32 (int8 only)
    v_scale: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[-3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


KV_QUANT_EPS = 1e-8


def quantize_kv_rows(seq: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-token-row int8: seq [..., KVH, Dh] -> (codes int8,
    scale f32 [...]) with scale = amax over the trailing [KVH, Dh] / 127."""
    f = seq.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=(-2, -1))
    scale = jnp.maximum(amax, KV_QUANT_EPS) / 127.0
    codes = jnp.clip(jnp.round(f / scale[..., None, None]), -127, 127)
    return codes.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_gathered(gathered: jnp.ndarray, scale: jnp.ndarray,
                        dtype=jnp.float32) -> jnp.ndarray:
    """Dequantize gathered int8 pages: gathered [..., ps, KVH, Dh] codes,
    scale [..., ps] -> fp values in ``dtype``."""
    return (gathered.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None, None]).astype(dtype)


def init_paged_kv_cache(batch: int, num_pages: int, table_width: int,
                        page_size: int, cfg: AttnConfig,
                        dtype=jnp.bfloat16,
                        kv_dtype=None) -> PagedKVCache:
    """``kv_dtype`` overrides the page storage dtype; ``jnp.int8`` turns
    on quantized storage (per-token-row f32 scales ride along)."""
    kv_dtype = dtype if kv_dtype is None else kv_dtype
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    quantized = jnp.dtype(kv_dtype) == jnp.dtype(jnp.int8)
    scale = (jnp.zeros((num_pages, page_size), jnp.float32)
             if quantized else None)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, kv_dtype),
        v_pages=jnp.zeros(shape, kv_dtype),
        page_table=jnp.zeros((batch, table_width), jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        k_scale=scale, v_scale=scale)


def _scatter_pages(pages: jnp.ndarray, page_table: jnp.ndarray,
                   seq: jnp.ndarray) -> jnp.ndarray:
    """Write [B,S,KVH,Dh] token rows into their pages.

    Position t of row b lands in physical page ``page_table[b, t//ps]`` at
    offset ``t%ps``.  S is padded up to a page multiple; positions whose
    table entry is unallocated (0) land in the null page — harmless, and
    rows never share live pages so the scatter has no real collisions.
    """
    b, s, kvh, dh = seq.shape
    ps = pages.shape[1]
    pad = (-s) % ps
    if pad:
        seq = jnp.pad(seq, ((0, 0), (0, pad), (0, 0), (0, 0)))
    npp = seq.shape[1] // ps
    npp_eff = min(npp, page_table.shape[1])
    tiles = seq[:, :npp_eff * ps].reshape(b, npp_eff, ps, kvh, dh)
    ids = page_table[:, :npp_eff].reshape(-1)
    return pages.at[ids].set(
        tiles.reshape(b * npp_eff, ps, kvh, dh).astype(pages.dtype))


def _scatter_scales(scales: jnp.ndarray, page_table: jnp.ndarray,
                    rows: jnp.ndarray) -> jnp.ndarray:
    """Page-tile twin of :func:`_scatter_pages` for [B,S] per-token scales
    landing in the [P, ps] scale pool."""
    b, s = rows.shape
    ps = scales.shape[1]
    pad = (-s) % ps
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    npp_eff = min(rows.shape[1] // ps, page_table.shape[1])
    tiles = rows[:, :npp_eff * ps].reshape(b, npp_eff, ps)
    ids = page_table[:, :npp_eff].reshape(-1)
    return scales.at[ids].set(
        tiles.reshape(b * npp_eff, ps).astype(scales.dtype))


def _scatter_pages_at(pages: jnp.ndarray, page_table: jnp.ndarray,
                      seq: jnp.ndarray, start: jnp.ndarray,
                      count: jnp.ndarray) -> jnp.ndarray:
    """Token-granular page scatter: token t of row b lands at logical
    position ``start[b] + t`` (suffix prefill after a prefix-cache hit —
    the shared prefix's pages are already populated and MUST NOT be
    rewritten).  Tokens with ``t >= count[b]`` (padding) are routed to the
    null page."""
    b, s, kvh, dh = seq.shape
    ps = pages.shape[1]
    np_w = page_table.shape[1]
    pos = start[:, None] + jnp.arange(s)[None, :]              # [B,S]
    logical = jnp.minimum(pos // ps, np_w - 1)
    ids = jnp.take_along_axis(page_table, logical, axis=1)     # [B,S]
    ids = jnp.where(jnp.arange(s)[None, :] < count[:, None], ids, 0)
    offs = pos % ps
    return pages.at[ids, offs].set(seq.astype(pages.dtype))


def _scatter_scales_at(scales: jnp.ndarray, page_table: jnp.ndarray,
                       rows: jnp.ndarray, start: jnp.ndarray,
                       count: jnp.ndarray) -> jnp.ndarray:
    """Token-granular twin of :func:`_scatter_scales`."""
    b, s = rows.shape
    ps = scales.shape[1]
    np_w = page_table.shape[1]
    pos = start[:, None] + jnp.arange(s)[None, :]
    logical = jnp.minimum(pos // ps, np_w - 1)
    ids = jnp.take_along_axis(page_table, logical, axis=1)
    ids = jnp.where(jnp.arange(s)[None, :] < count[:, None], ids, 0)
    return scales.at[ids, pos % ps].set(rows.astype(scales.dtype))


def _gather_ctx(cache: PagedKVCache, dtype) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """Dense [B, NP*ps, KVH, Dh] view of every page each row's table
    lists, dequantized when the cache stores int8 codes."""
    b = cache.page_table.shape[0]
    ps, kvh, dh = cache.k_pages.shape[1:]
    np_w = cache.page_table.shape[1]
    k_g = cache.k_pages[cache.page_table]       # [B, NP, ps, KVH, Dh]
    v_g = cache.v_pages[cache.page_table]
    if cache.quantized:
        k_g = dequantize_gathered(k_g, cache.k_scale[cache.page_table],
                                  dtype)
        v_g = dequantize_gathered(v_g, cache.v_scale[cache.page_table],
                                  dtype)
    return (k_g.reshape(b, np_w * ps, kvh, dh).astype(dtype),
            v_g.reshape(b, np_w * ps, kvh, dh).astype(dtype))


def _suffix_prefill_attend(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                           cache: PagedKVCache, prefix_len: jnp.ndarray,
                           lengths: jnp.ndarray,
                           positions3: Optional[jnp.ndarray] = None):
    """Prefill of a DIVERGENT SUFFIX against an already-resident prefix.

    Query token i of row b sits at absolute position ``prefix_len[b]+i``:
    it attends every resident prefix key (gathered from the slot's pages,
    dequantized if int8) plus the causal span of the suffix itself.
    Returns (attn out, k_suffix, v_suffix) — only suffix K/V need to be
    written back, the prefix pages are shared/read-only.
    """
    b, s, _ = x.shape
    positions = prefix_len[:, None] + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    with jax.named_scope("kv_cache"):
        k_ctx, v_ctx = _gather_ctx(cache, q.dtype)
    ctx_w = k_ctx.shape[1]
    # joint mask over [ctx | suffix] keys: ctx key j real iff j < prefix;
    # suffix key t visible iff t <= i (causal) and t < suffix length
    ctx_ok = jnp.broadcast_to(
        (jnp.arange(ctx_w)[None, :] < prefix_len[:, None])[:, None, :],
        (b, s, ctx_w))
    suf_ok = ((jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])[None]
              & (jnp.arange(s)[None, None, :] < lengths[:, None, None]))
    mask = jnp.concatenate([ctx_ok, suf_ok], axis=-1)   # [B, S, ctx+S]
    k_all = jnp.concatenate([k_ctx, k], axis=1)
    v_all = jnp.concatenate([v_ctx, v], axis=1)
    scores = _gqa_scores(q, k_all).astype(jnp.float32)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v_all), k, v


def prefill_into_paged_cache(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                             cache: PagedKVCache,
                             positions3: Optional[jnp.ndarray] = None,
                             lengths: Optional[jnp.ndarray] = None,
                             prefix_len: Optional[jnp.ndarray] = None
                             ) -> Tuple[jnp.ndarray, PagedKVCache]:
    """:func:`prefill_into_cache` with the K/V landing in pages.

    Identical attention compute (same dispatch, same ragged ``lengths``
    masking); only the cache write differs — each row's K/V tokens are
    scattered into the pages its table already lists (the pool allocates
    them before the prefill program runs).  int8 caches quantize each
    token row on the way in (one f32 scale per token).

    ``prefix_len`` [B] switches to SUFFIX mode (prefix-cache hit): ``x``
    holds only the divergent suffix, queries run at absolute positions
    ``prefix_len + i`` against resident-prefix + suffix keys, and the
    scatter is token-granular starting at ``prefix_len`` so the shared
    prefix pages are never rewritten.
    """
    b, s, _ = x.shape
    if prefix_len is None:
        out, k, v = _prefill_qkv_attend(p, x, cfg, positions3, lengths)
        suffix_len = (_row_lengths(lengths, b) if lengths is not None
                      else jnp.full((b,), s, jnp.int32))
        new_len = suffix_len
        start = jnp.zeros((b,), jnp.int32)
    else:
        prefix_len = _row_lengths(prefix_len, b)
        suffix_len = (_row_lengths(lengths, b) if lengths is not None
                      else jnp.full((b,), s, jnp.int32))
        out, k, v = _suffix_prefill_attend(p, x, cfg, cache, prefix_len,
                                           suffix_len, positions3)
        new_len = prefix_len + suffix_len
        start = prefix_len
    with jax.named_scope("kv_cache"):
        if cache.quantized:
            k_codes, k_sc = quantize_kv_rows(k)
            v_codes, v_sc = quantize_kv_rows(v)
            newk = _scatter_pages_at(cache.k_pages, cache.page_table,
                                     k_codes, start, suffix_len)
            newv = _scatter_pages_at(cache.v_pages, cache.page_table,
                                     v_codes, start, suffix_len)
            new_ks = _scatter_scales_at(cache.k_scale, cache.page_table,
                                        k_sc, start, suffix_len)
            new_vs = _scatter_scales_at(cache.v_scale, cache.page_table,
                                        v_sc, start, suffix_len)
        elif prefix_len is None:
            newk = _scatter_pages(cache.k_pages, cache.page_table, k)
            newv = _scatter_pages(cache.v_pages, cache.page_table, v)
            new_ks, new_vs = cache.k_scale, cache.v_scale
        else:
            newk = _scatter_pages_at(cache.k_pages, cache.page_table, k,
                                     start, suffix_len)
            newv = _scatter_pages_at(cache.v_pages, cache.page_table, v,
                                     start, suffix_len)
            new_ks, new_vs = cache.k_scale, cache.v_scale
    new_cache = PagedKVCache(k_pages=newk, v_pages=newv,
                             page_table=cache.page_table, length=new_len,
                             k_scale=new_ks, v_scale=new_vs)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, new_cache


def paged_decode_jnp(q: jnp.ndarray, k_pages: jnp.ndarray,
                     v_pages: jnp.ndarray, page_table: jnp.ndarray,
                     length: jnp.ndarray, k_new: jnp.ndarray,
                     v_new: jnp.ndarray,
                     k_scale: Optional[jnp.ndarray] = None,
                     v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The gather-based paged decode reference (dispatch ``jnp_paged``;
    with scales, ``jnp_paged_q8``).

    Gathers each row's listed pages into a dense [B, NP*ps, KVH, Dh]
    context view (dequantizing int8 codes with the per-token scales) and
    runs the SAME two-part softmax as the dense decode path
    (:func:`_decode_token_attend`) — the masked-dense oracle the Pallas
    kernels are checked against, and the interpret-mode fallback.
    """
    b = q.shape[0]
    ps, kvh, dh = k_pages.shape[1], k_pages.shape[2], k_pages.shape[3]
    np_w = page_table.shape[1]
    k_ctx = k_pages[page_table]
    v_ctx = v_pages[page_table]
    if k_scale is not None:
        k_ctx = dequantize_gathered(k_ctx, k_scale[page_table], q.dtype)
        v_ctx = dequantize_gathered(v_ctx, v_scale[page_table], q.dtype)
    k_ctx = k_ctx.reshape(b, np_w * ps, kvh, dh)
    v_ctx = v_ctx.reshape(b, np_w * ps, kvh, dh)
    valid = jnp.arange(np_w * ps)[None, :] < length[:, None]
    return _decode_token_attend(q, k_ctx, v_ctx, valid, k_new, v_new)


def paged_decode_attention_token(p: Params, x: jnp.ndarray, cfg: AttnConfig,
                                 k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                                 page_table: jnp.ndarray,
                                 length: jnp.ndarray,
                                 positions3: Optional[jnp.ndarray] = None,
                                 k_scale: Optional[jnp.ndarray] = None,
                                 v_scale: Optional[jnp.ndarray] = None
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """One-token decode against READ-ONLY pages: the paged twin of
    :func:`decode_attention_token`.

    Attention touches only the pages each row's table lists — bytes/token
    is O(length), not O(max_seq).  Which implementation runs (the Pallas
    paged kernel or the gather reference, in their fp or int8-dequant
    variants) is a registry decision (``registry.select("paged_decode",
    quantized=...)``); the new token's K/V are returned UNQUANTIZED for
    the caller to scatter into its page (quantizing on the way if the
    cache is int8).
    """
    b = x.shape[0]
    length = _row_lengths(length, b)
    positions = length[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    from repro.kernels import registry
    quantized = k_scale is not None
    impl = registry.select("paged_decode", quantized=quantized)
    kw = dict(k_scale=k_scale, v_scale=v_scale) if quantized else {}
    out = registry.run("paged_decode", q, k_pages, v_pages, page_table,
                       length, k, v, impl=impl, **kw)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, k, v
