"""Transformer decoder blocks + the weight-stacked scan machinery.

One :class:`BlockConfig` describes a block (attention flavor + MLP flavor);
``init_stacked``/``apply_stack`` stack L of them on a leading "layers" axis
and run them under ``lax.scan`` (features.scan_layers) with the remat policy
from :class:`repro.core.features.FeatureSet` — this is what keeps the
88-layer mistral-large HLO compact enough to dry-run.

The same block machinery serves dense archs, MoE archs (mlp="moe"), the
VLM backbone (mrope in AttnConfig) and the enc-dec decoder (cross-attention
block in encdec.py composes these pieces).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.features import FeatureSet
from repro.models import attention as attn_mod
from repro.models.attention import AttnConfig, KVCache
from repro.models.layers import (DEFAULT_RULES, Params, ShardingRules, Specs,
                                 constrain, dense_init, layer_norm,
                                 layernorm_init, rms_norm, rmsnorm_init,
                                 swiglu, truncated_normal_init)
from repro.models.moe import (MoEConfig, init_moe, moe_mlp,
                              moe_mlp_capacity, moe_specs)

__all__ = ["BlockConfig", "init_block", "block_specs", "apply_block",
           "init_stacked", "stacked_specs", "apply_stack",
           "apply_stack_decode", "remat_policy_fn"]


class BlockConfig(NamedTuple):
    attn: AttnConfig
    d_ff: int
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    mlp: str = "swiglu"          # swiglu | moe
    moe: Optional[MoEConfig] = None
    norm_eps: float = 1e-6


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: BlockConfig, dtype=jnp.float32) -> Params:
    ka, km = jax.random.split(key)
    d = cfg.attn.d_model
    norm_init = rmsnorm_init if cfg.norm == "rmsnorm" else layernorm_init
    p: Params = {
        "ln1": norm_init(d),
        "attn": attn_mod.init_attn(ka, cfg.attn, dtype),
        "ln2": norm_init(d),
    }
    if cfg.mlp == "moe":
        assert cfg.moe is not None
        p["moe"] = init_moe(km, cfg.moe, dtype)
    else:
        k1, k2, k3 = jax.random.split(km, 3)
        import numpy as np
        std = 1.0 / np.sqrt(d)
        p["mlp"] = {
            "w_gate": truncated_normal_init(k1, (d, cfg.d_ff), dtype, std),
            "w_up": truncated_normal_init(k2, (d, cfg.d_ff), dtype, std),
            "w_down": truncated_normal_init(k3, (cfg.d_ff, d), dtype,
                                            1.0 / np.sqrt(cfg.d_ff)),
        }
    return p


def block_specs(cfg: BlockConfig) -> Specs:
    norm_spec = ({"scale": ("act_embed",)} if cfg.norm == "rmsnorm"
                 else {"scale": ("act_embed",), "bias": ("act_embed",)})
    s: Specs = {
        "ln1": dict(norm_spec),
        "attn": attn_mod.attn_specs(cfg.attn),
        "ln2": dict(norm_spec),
    }
    if cfg.mlp == "moe":
        s["moe"] = moe_specs(cfg.moe)
    else:
        s["mlp"] = {"w_gate": ("embed", "ff"), "w_up": ("embed", "ff"),
                    "w_down": ("ff", "embed")}
    return s


def _norm(x, p, cfg: BlockConfig):
    return (rms_norm(x, p, cfg.norm_eps) if cfg.norm == "rmsnorm"
            else layer_norm(x, p, cfg.norm_eps))


def apply_block(p: Params, x: jnp.ndarray, cfg: BlockConfig, *,
                rules: ShardingRules = DEFAULT_RULES, mesh=None,
                positions3=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-norm block, train/prefill path.  Returns (y, aux_loss)."""
    x = constrain(x, ("batch", "act_seq", "act_embed"), rules, mesh)
    h = x + attn_mod.attention(p["attn"], _norm(x, p["ln1"], cfg), cfg.attn,
                               positions3=positions3)
    h = constrain(h, ("batch", "act_seq", "act_embed"), rules, mesh)
    aux = jnp.zeros((), jnp.float32)
    if cfg.mlp == "moe":
        cst = (lambda a, axes: constrain(a, axes, rules, mesh, soft=True))
        m, aux = moe_mlp_capacity(p["moe"], _norm(h, p["ln2"], cfg),
                                  cfg.moe, constrain_fn=cst)
    else:
        mp = p["mlp"]
        m = swiglu(_norm(h, p["ln2"], cfg), mp["w_gate"].astype(x.dtype),
                   mp["w_up"].astype(x.dtype), mp["w_down"].astype(x.dtype))
    y = h + m
    y = constrain(y, ("batch", "act_seq", "act_embed"), rules, mesh)
    return y, aux


def _block_mlp(p: Params, h: jnp.ndarray, cfg: BlockConfig,
               valid=None) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """The post-attention MLP half of a block (aux loss dropped — the
    decode/prefill paths never train), under the ``mlp`` scope.  Returns
    (out, the MoE layer's counts — None for a dense MLP).  ``valid``
    [B,S] marks the tokens an MoE layer routes."""
    with jax.named_scope("mlp"):
        if cfg.mlp == "moe":
            m, _, counts = moe_mlp(p["moe"], _norm(h, p["ln2"], cfg),
                                   cfg.moe, valid=valid)
            return m, counts
        mp = p["mlp"]
        return swiglu(_norm(h, p["ln2"], cfg), mp["w_gate"].astype(h.dtype),
                      mp["w_up"].astype(h.dtype),
                      mp["w_down"].astype(h.dtype)), None


def apply_block_decode(p: Params, x: jnp.ndarray, cfg: BlockConfig,
                       cache: KVCache, *, rules=DEFAULT_RULES, mesh=None,
                       positions3=None):
    """One-token decode through one block: (y, new cache, MoE counts or
    None)."""
    with jax.named_scope("attention"):
        a, new_cache = attn_mod.decode_attention(
            p["attn"], _norm(x, p["ln1"], cfg), cfg.attn, cache,
            positions3=positions3)
    h = x + a
    m, counts = _block_mlp(p, h, cfg)
    return h + m, new_cache, counts


def apply_block_prefill(p: Params, x: jnp.ndarray, cfg: BlockConfig,
                        cache, *, rules=DEFAULT_RULES, mesh=None,
                        positions3=None, lengths=None, prefix_len=None):
    """Prefill one block; ``cache`` may be dense (:class:`KVCache`) or
    paged (:class:`~repro.models.attention.PagedKVCache`) — the attention
    compute is identical, only the K/V landing zone differs.  Returns (y,
    new cache, MoE counts or None); with ``lengths`` an MoE layer routes
    only each row's real tokens.

    ``prefix_len`` [B] (paged only) marks a resident shared prefix: ``x``
    is the divergent suffix, attention spans prefix pages + suffix."""
    paged = isinstance(cache, attn_mod.PagedKVCache)
    if prefix_len is not None and not paged:
        raise ValueError("prefix_len requires a paged KV cache "
                         "(dense prefill has no resident prefix)")
    with jax.named_scope("attention"):
        if paged:
            a, new_cache = attn_mod.prefill_into_paged_cache(
                p["attn"], _norm(x, p["ln1"], cfg), cfg.attn, cache,
                positions3=positions3, lengths=lengths,
                prefix_len=prefix_len)
        else:
            a, new_cache = attn_mod.prefill_into_cache(
                p["attn"], _norm(x, p["ln1"], cfg), cfg.attn, cache,
                positions3=positions3, lengths=lengths)
    h = x + a
    valid = (None if lengths is None else
             jnp.arange(x.shape[1])[None, :] < jnp.reshape(lengths, (-1, 1)))
    m, counts = _block_mlp(p, h, cfg, valid)
    return h + m, new_cache, counts


# ---------------------------------------------------------------------------
# stacking
# ---------------------------------------------------------------------------

def init_stacked(key, n_layers: int, init_one: Callable[[Any], Params]
                 ) -> Params:
    """vmap the per-layer init over layer keys -> leading 'layers' axis."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_one)(keys)


def stacked_specs(one: Specs) -> Specs:
    """Prepend the 'layers' logical axis to every leaf spec."""
    return jax.tree.map(lambda ax: ("layers",) + tuple(ax), one,
                        is_leaf=lambda x: isinstance(x, tuple))


def remat_policy_fn(features: FeatureSet):
    cp = jax.checkpoint_policies
    return {
        "none": None,
        "dots": cp.checkpoint_dots,
        "dots_no_batch": cp.checkpoint_dots_with_no_batch_dims,
        "full": cp.nothing_saveable,
    }[features.remat_policy]


def apply_stack(stacked: Params, x: jnp.ndarray, cfg: BlockConfig,
                features: FeatureSet, *, rules=DEFAULT_RULES, mesh=None,
                positions3=None,
                block_fn=apply_block) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run L stacked blocks; returns (y, summed aux loss)."""

    def one(layer_p, h):
        return block_fn(layer_p, h, cfg, rules=rules, mesh=mesh,
                        positions3=positions3)

    policy = remat_policy_fn(features)
    if features.remat_policy != "none":
        one = jax.checkpoint(one, policy=policy)

    if features.scan_layers:
        def body(carry, layer_p):
            h, aux = carry
            y, a = one(layer_p, h)
            return (y, aux + a), None
        (y, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), stacked,
            unroll=features.scan_unroll)
        return y, aux
    # unrolled python loop (debug / tiny configs)
    n = jax.tree.leaves(stacked)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    h = x
    for i in range(n):
        layer_p = jax.tree.map(lambda a: a[i], stacked)
        h, a = one(layer_p, h)
        aux = aux + a
    return h, aux


def apply_stack_decode(stacked: Params, x: jnp.ndarray, cfg: BlockConfig,
                       caches: KVCache, features: FeatureSet, *,
                       rules=DEFAULT_RULES, mesh=None, positions3=None,
                       block_fn=apply_block_decode):
    """Decode through stacked blocks; caches carry a leading layers axis.
    Returns (y, new caches, the MoE layers' counts summed over the layers
    — None for dense MLPs).

    The scan path threads the WHOLE stacked cache through the carry and
    writes one token per layer with an in-place dynamic-update-slice (while
    -loop aliasing).  Scanning caches as xs and re-stacking them as ys — the
    obvious form — rewrites each layer's full [B,S,KVH,Dh] slice every
    decoded token (§Perf hillclimb 3: 53 GB/step on mistral-large).

    A paged cache (:class:`~repro.models.attention.PagedKVCache`) takes its
    own path: per-layer paged decode attention over the page table, plus a
    single-page token write — bytes/token O(length), not O(max_seq).

    The stack runs under the ``layers`` scope: the scan's own slicing and
    re-stacking of the per-layer caches carries it, and the blocks' parts
    their ``attention`` / ``mlp`` / ``kv_cache`` scopes inside it.
    """
    with jax.named_scope("layers"):
        return _apply_stack_decode(stacked, x, cfg, caches, features,
                                   rules=rules, mesh=mesh,
                                   positions3=positions3, block_fn=block_fn)


def _apply_stack_decode(stacked, x, cfg, caches, features, *, rules, mesh,
                        positions3, block_fn):
    if isinstance(caches, attn_mod.PagedKVCache) \
            and block_fn is apply_block_decode:
        return _apply_stack_decode_paged(stacked, x, cfg, caches, features,
                                         rules=rules, mesh=mesh,
                                         positions3=positions3)
    if features.scan_layers and features.decode_inplace_cache \
            and block_fn is apply_block_decode:
        b = x.shape[0]
        length = attn_mod._row_lengths(
            caches.length[0] if caches.length.ndim > 1 else caches.length, b)
        n = jax.tree.leaves(stacked)[0].shape[0]
        rows = jnp.arange(b)

        def body(carry, scanned):
            h, kst, vst = carry
            i, layer_p = scanned
            with jax.named_scope("kv_cache"):
                k_l = jax.lax.dynamic_index_in_dim(kst, i, 0, keepdims=False)
                v_l = jax.lax.dynamic_index_in_dim(vst, i, 0, keepdims=False)
            with jax.named_scope("attention"):
                a, k_t, v_t = attn_mod.decode_attention_token(
                    layer_p["attn"], _norm(h, layer_p["ln1"], cfg),
                    cfg.attn, k_l, v_l, length, positions3=positions3)
            h2 = h + a
            m, counts = _block_mlp(layer_p, h2, cfg)
            # per-row scatter: row b's token lands at its own length[b]
            with jax.named_scope("kv_cache"):
                kst = kst.at[i, rows, length].set(
                    k_t[:, 0].astype(kst.dtype))
                vst = vst.at[i, rows, length].set(
                    v_t[:, 0].astype(vst.dtype))
            return (h2 + m, kst, vst), counts

        (y, kst, vst), counts = jax.lax.scan(
            body, (x, caches.k, caches.v), (jnp.arange(n), stacked))
        return (y, KVCache(k=kst, v=vst, length=caches.length + 1),
                _layer_sum(counts))

    def body(h, scanned):
        layer_p, layer_cache = scanned
        y, new_cache, counts = block_fn(layer_p, h, cfg, layer_cache,
                                        rules=rules, mesh=mesh,
                                        positions3=positions3)
        return y, (new_cache, counts)

    if features.scan_layers:
        y, (new_caches, counts) = jax.lax.scan(body, x, (stacked, caches))
        return y, new_caches, _layer_sum(counts)
    n = jax.tree.leaves(stacked)[0].shape[0]
    h = x
    outs = []
    for i in range(n):
        layer_p = jax.tree.map(lambda a: a[i], stacked)
        layer_cache = jax.tree.map(lambda a: a[i], caches)
        h, out = body(h, (layer_p, layer_cache))
        outs.append(out)
    new_caches, counts = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return h, new_caches, _layer_sum(counts)


def _layer_sum(counts):
    """MoE counts stacked over the layers ([L, 4]) summed; None (dense
    MLPs) stays None."""
    return None if counts is None else jnp.sum(counts, axis=0)


def _apply_stack_decode_paged(stacked: Params, x: jnp.ndarray,
                              cfg: BlockConfig,
                              caches: "attn_mod.PagedKVCache",
                              features: FeatureSet, *,
                              rules=DEFAULT_RULES, mesh=None,
                              positions3=None):
    """One-token decode through stacked blocks over PAGED caches.

    Pages are carried in place (scan carry, while-loop aliasing, exactly
    like the dense in-place path); the page table and per-row lengths are
    shared across layers (every layer's slice holds the same values, so
    layer 0's are read once).  The token write touches ONE page per layer:
    row b's token lands in physical page ``pt[b, length[b] // ps]`` at
    offset ``length[b] % ps`` — the pool guarantees that page is
    allocated before the segment runs.  The layer's pool slices and the
    token write run under the ``kv_cache`` scope.
    """
    b = x.shape[0]
    length = attn_mod._row_lengths(
        caches.length[0] if caches.length.ndim > 1 else caches.length, b)
    pt = (caches.page_table[0] if caches.page_table.ndim > 2
          else caches.page_table)
    ps = caches.k_pages.shape[-3]
    np_w = pt.shape[-1]
    rows = jnp.arange(b)
    with jax.named_scope("kv_cache"):
        page = pt[rows, jnp.minimum(length // ps, np_w - 1)]
        off = length % ps
    live = (pt[:, :1] != 0)                                  # [B, 1]
    n = jax.tree.leaves(stacked)[0].shape[0]
    quantized = caches.quantized

    def attend(h, layer_p, k_l, v_l, ksc_l=None, vsc_l=None):
        with jax.named_scope("attention"):
            a, k_t, v_t = attn_mod.paged_decode_attention_token(
                layer_p["attn"], _norm(h, layer_p["ln1"], cfg), cfg.attn,
                k_l, v_l, pt, length, positions3=positions3,
                k_scale=ksc_l, v_scale=vsc_l)
        h2 = h + a
        m, counts = _block_mlp(layer_p, h2, cfg, live)
        return h2 + m, k_t, v_t, counts

    def layer_slice(pool, i):
        with jax.named_scope("kv_cache"):
            return jax.lax.dynamic_index_in_dim(pool, i, 0, keepdims=False)

    def write(pool, i, rows_kv):
        with jax.named_scope("kv_cache"):
            return pool.at[i, page, off].set(rows_kv.astype(pool.dtype))

    if quantized:
        # int8 cache: attend with the layer's scales, then quantize the
        # fresh token's K/V row on the append write (one scale per row)
        def body(carry, scanned):
            h, kst, vst, ksc, vsc = carry
            i, layer_p = scanned
            y, k_t, v_t, counts = attend(
                h, layer_p, layer_slice(kst, i), layer_slice(vst, i),
                layer_slice(ksc, i), layer_slice(vsc, i))
            with jax.named_scope("kv_cache"):
                k_c, k_s = attn_mod.quantize_kv_rows(k_t[:, 0])
                v_c, v_s = attn_mod.quantize_kv_rows(v_t[:, 0])
            kst, vst = write(kst, i, k_c), write(vst, i, v_c)
            ksc, vsc = write(ksc, i, k_s), write(vsc, i, v_s)
            return (y, kst, vst, ksc, vsc), counts

        carry0 = (x, caches.k_pages, caches.v_pages,
                  caches.k_scale, caches.v_scale)
    else:
        def body(carry, scanned):
            h, kst, vst = carry
            i, layer_p = scanned
            y, k_t, v_t, counts = attend(h, layer_p, layer_slice(kst, i),
                                         layer_slice(vst, i))
            kst = write(kst, i, k_t[:, 0])
            vst = write(vst, i, v_t[:, 0])
            return (y, kst, vst), counts

        carry0 = (x, caches.k_pages, caches.v_pages)

    if features.scan_layers:
        (y, *pools), counts = jax.lax.scan(body, carry0,
                                           (jnp.arange(n), stacked))
    else:
        carry, per_layer = carry0, []
        for i in range(n):
            layer_p = jax.tree.map(lambda a: a[i], stacked)
            carry, c = body(carry, (jnp.asarray(i), layer_p))
            per_layer.append(c)
        y, *pools = carry
        counts = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    kst, vst = pools[0], pools[1]
    ksc, vsc = (pools[2], pools[3]) if quantized else (None, None)
    return y, attn_mod.PagedKVCache(k_pages=kst, v_pages=vst,
                                    page_table=caches.page_table,
                                    length=caches.length + 1,
                                    k_scale=ksc, v_scale=vsc), \
        _layer_sum(counts)
