"""Unified causal-LM interface over every assigned architecture family.

One :class:`LMConfig` + one :class:`LM` object expose ``init``, ``forward``,
``loss`` (training), ``init_decode_state`` / ``prefill`` / ``decode_step``
(serving) for:

========== ================================================================
family     assembly
========== ================================================================
dense      embed -> scan(transformer blocks) -> norm -> lm_head
moe        dense with mlp="moe" blocks (EP-sharded experts)
vlm        dense with M-RoPE; patch embeddings (frontend STUB) replace the
           first n_patch token embeddings
xlstm      embed -> scan(mLSTM/sLSTM block pairs) -> norm -> head
hybrid     embed -> [attn_every x mamba2, shared transformer block]* -> head
encdec     frontend-stub src embeddings -> scan(enc) ;
           tgt embed -> scan(dec w/ cross-attention) -> head
========== ================================================================

Sharding: every param/state tree has a twin logical-axis spec tree;
``LM.param_pspecs(mesh)`` resolves them through the active
:class:`repro.models.layers.ShardingRules` — the knob the §Perf hillclimb
turns.  Loss constrains logits to ("batch","act_seq","vocab") so the
[B,S,V] tensor stays vocab-sharded through the softmax (all-reduce of max
and sum instead of a 40 GB replicated tensor).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.features import FeatureSet, default_features
from repro.models import attention as attn_mod
from repro.models import encdec as encdec_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import AttnConfig, KVCache
from repro.models.layers import (DEFAULT_RULES, Params, ShardingRules, Specs,
                                 constrain, count_params, embed_init,
                                 rms_norm, rmsnorm_init, layer_norm,
                                 layernorm_init, spec_tree_to_pspecs,
                                 truncated_normal_init)
from repro.models.moe import COUNTS, MoEConfig, count_active_params
from repro.models.ssm import Mamba2Config
from repro.models.transformer import BlockConfig
from repro.models.xlstm import XLSTMConfig

__all__ = ["LMConfig", "LM", "Batch", "MOE_COUNTS", "MOE_KINDS"]

Batch = Dict[str, jnp.ndarray]

#: an MoE model's decode state carries its layers' counts (``moe.COUNTS``,
#: summed over the layers) under this key, int32 [kind, count]: one row
#: for :meth:`LM.decode_step`, one for :meth:`LM.prefill`, each growing by
#: what the call routed (and wrapping past 2**31)
MOE_COUNTS = "moe_counts"
MOE_KINDS = ("decode", "prefill")


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    family: str                  # dense | moe | vlm | xlstm | hybrid | encdec
    vocab: int
    d_model: int
    n_layers: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    head_dim: int = 0            # 0 -> d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False        # per-head RMSNorm on q and k (Qwen3)
    norm: str = "rmsnorm"
    tie_embeddings: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # --- moe ---
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_shared_experts: int = 0
    moe_d_ff_shared: int = 0
    # the routed experts this model holds (one chip's share under expert
    # parallelism): moe_first_expert .. + moe_held_experts - 1; 0 = all
    moe_first_expert: int = 0
    moe_held_experts: int = 0
    # --- vlm ---
    mrope_sections: Tuple[int, int, int] = ()
    n_patches: int = 0           # patch positions at sequence start (stub)
    patch_grid: Tuple[int, int] = (16, 16)
    # --- hybrid (zamba2) ---
    ssm_state: int = 64
    ssm_head_dim: int = 64
    attn_every: int = 6
    # --- encdec ---
    enc_layers: int = 0
    src_ratio: int = 4           # S_src = S // src_ratio (audio downsampling)
    # --- scan/kernels ---
    chunk_size: int = 256        # attention q-chunk / ssd chunk
    attn_chunk_threshold: int = 4096
    attn_softmax: str = "naive"  # "naive" (paper-faithful) | "fused" (§Perf)

    # ------------------------------------------------------------ derived
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def attn_config(self, causal: bool = True) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, qk_norm=self.qk_norm,
            norm_eps=self.norm_eps, causal=causal,
            rope_theta=self.rope_theta,
            mrope_sections=self.mrope_sections or None,
            chunk_size=self.chunk_size,
            chunk_threshold=self.attn_chunk_threshold,
            softmax_mode=self.attn_softmax)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff_expert=self.d_ff,
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            num_shared_experts=self.moe_shared_experts,
            d_ff_shared=self.moe_d_ff_shared,
            first_expert=self.moe_first_expert,
            held_experts=self.moe_held_experts)

    def block_config(self) -> BlockConfig:
        return BlockConfig(
            attn=self.attn_config(), d_ff=self.d_ff, norm=self.norm,
            mlp="moe" if self.family == "moe" else "swiglu",
            moe=self.moe_config() if self.family == "moe" else None,
            norm_eps=self.norm_eps)

    def xlstm_config(self) -> XLSTMConfig:
        return XLSTMConfig(d_model=self.d_model, num_heads=self.num_heads,
                           chunk_size=self.chunk_size, norm_eps=self.norm_eps)

    def mamba_config(self) -> Mamba2Config:
        return Mamba2Config(d_model=self.d_model, d_state=self.ssm_state,
                            head_dim=self.ssm_head_dim,
                            chunk_size=self.chunk_size,
                            norm_eps=self.norm_eps)

    def encdec_config(self) -> encdec_mod.CrossAttnBlockConfig:
        return encdec_mod.CrossAttnBlockConfig(
            attn=self.attn_config(), d_ff=self.d_ff, norm_eps=self.norm_eps)

    @property
    def sub_quadratic(self) -> bool:
        """Whether long_500k decode is O(1)-state (xlstm/hybrid)."""
        return self.family in ("xlstm", "hybrid")


class LM:
    """The model object: pure-function apply methods over a params pytree."""

    def __init__(self, cfg: LMConfig,
                 features: Optional[FeatureSet] = None,
                 rules: ShardingRules = DEFAULT_RULES,
                 mesh=None, dtype=jnp.bfloat16):
        self.cfg = cfg
        self.features = features or default_features()
        self.rules = rules
        self.mesh = mesh
        self.dtype = dtype

    # ================================================================ init
    def init(self, rng) -> Params:
        cfg = self.cfg
        k_embed, k_blocks, k_head, k_extra = jax.random.split(rng, 4)
        p: Params = {"embed": embed_init(k_embed, cfg.vocab, cfg.d_model)}
        norm_init = rmsnorm_init if cfg.norm == "rmsnorm" else layernorm_init
        p["final_norm"] = norm_init(cfg.d_model)
        if not cfg.tie_embeddings:
            p["lm_head"] = {"w": truncated_normal_init(
                k_head, (cfg.d_model, cfg.vocab), jnp.float32,
                1.0 / np.sqrt(cfg.d_model))}

        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            bc = cfg.block_config()
            p["blocks"] = tf_mod.init_stacked(
                k_blocks, cfg.n_layers,
                lambda k: tf_mod.init_block(k, bc, jnp.float32))
        elif fam == "xlstm":
            xc = cfg.xlstm_config()
            n_pairs = cfg.n_layers // 2
            km, ks = jax.random.split(k_blocks)
            p["mlstm"] = tf_mod.init_stacked(
                km, n_pairs, lambda k: xlstm_mod.init_mlstm_block(k, xc))
            p["slstm"] = tf_mod.init_stacked(
                ks, n_pairs, lambda k: xlstm_mod.init_slstm_block(k, xc))
        elif fam == "hybrid":
            mc = cfg.mamba_config()
            km, ka = jax.random.split(k_blocks)
            p["mamba"] = tf_mod.init_stacked(
                km, cfg.n_layers, lambda k: ssm_mod.init_mamba2_block(k, mc))
            p["shared_attn"] = tf_mod.init_block(ka, cfg.block_config())
        elif fam == "encdec":
            ec = cfg.encdec_config()
            ke, kd = jax.random.split(k_blocks)
            enc_cfg = ec._replace(attn=ec.attn._replace(causal=False))
            p["encoder"] = tf_mod.init_stacked(
                ke, cfg.enc_layers or cfg.n_layers,
                lambda k: encdec_mod.init_encoder_block(k, enc_cfg))
            p["decoder"] = tf_mod.init_stacked(
                kd, cfg.n_layers,
                lambda k: encdec_mod.init_decoder_block(k, ec))
            p["enc_final_norm"] = layernorm_init(cfg.d_model)
        else:
            raise ValueError(f"unknown family {fam!r}")
        return p

    def init_params(self, rng, *, mesh=None, dtype=None) -> Params:
        """:meth:`init` as one jitted program whose outputs are created
        where they live: sharded over ``mesh`` by :meth:`param_pspecs`
        when one is given (no device holds the whole tree first), and
        cast to ``dtype`` on the way out (e.g. bf16 serving weights)."""
        def init(key):
            p = self.init(key)
            if dtype is None:
                return p
            return jax.tree.map(lambda x: x.astype(dtype), p)

        if mesh is None:
            return jax.jit(init)(rng)
        from jax.sharding import NamedSharding
        pspecs = self.param_pspecs(mesh, jax.eval_shape(init, rng))
        return jax.jit(init, out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs))(rng)

    def param_specs(self) -> Specs:
        cfg = self.cfg
        s: Specs = {"embed": {"table": ("vocab", "embed")}}
        norm_spec = ({"scale": ("act_embed",)} if cfg.norm == "rmsnorm"
                     else {"scale": ("act_embed",), "bias": ("act_embed",)})
        s["final_norm"] = dict(norm_spec)
        if not cfg.tie_embeddings:
            s["lm_head"] = {"w": ("embed", "vocab")}
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            s["blocks"] = tf_mod.stacked_specs(
                tf_mod.block_specs(cfg.block_config()))
        elif fam == "xlstm":
            xc = cfg.xlstm_config()
            s["mlstm"] = tf_mod.stacked_specs(xlstm_mod.mlstm_block_specs(xc))
            s["slstm"] = tf_mod.stacked_specs(xlstm_mod.slstm_block_specs(xc))
        elif fam == "hybrid":
            mc = cfg.mamba_config()
            s["mamba"] = tf_mod.stacked_specs(ssm_mod.mamba2_block_specs(mc))
            s["shared_attn"] = tf_mod.block_specs(cfg.block_config())
        elif fam == "encdec":
            ec = cfg.encdec_config()
            s["encoder"] = tf_mod.stacked_specs(
                encdec_mod.encoder_block_specs(ec))
            s["decoder"] = tf_mod.stacked_specs(
                encdec_mod.decoder_block_specs(ec))
            s["enc_final_norm"] = {"scale": ("act_embed",),
                                   "bias": ("act_embed",)}
        return s

    def param_pspecs(self, mesh, params_shape: Optional[Params] = None):
        return spec_tree_to_pspecs(self.param_specs(), self.rules, mesh,
                                   shapes=params_shape)

    # ============================================================ backbone
    def _embed(self, p: Params, tokens: jnp.ndarray,
               patch_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        x = p["embed"]["table"].astype(self.dtype)[tokens]
        if self.cfg.family == "vlm" and patch_embeds is not None:
            np_ = patch_embeds.shape[1]
            x = jnp.concatenate([patch_embeds.astype(self.dtype),
                                 x[:, np_:]], axis=1)
        return x

    def _head(self, p: Params, x: jnp.ndarray) -> jnp.ndarray:
        """Final norm and LM head, under the ``head`` scope."""
        norm = rms_norm if self.cfg.norm == "rmsnorm" else layer_norm
        with jax.named_scope("head"):
            x = norm(x, p["final_norm"], self.cfg.norm_eps)
            w = (p["embed"]["table"].T if self.cfg.tie_embeddings
                 else p["lm_head"]["w"])
            logits = jnp.einsum("bsd,dv->bsv", x, w.astype(self.dtype))
            return constrain(logits, ("batch", "act_seq", "vocab"),
                             self.rules, self.mesh)

    def _vlm_positions3(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """M-RoPE position streams [3,B,S]: patches get (0,h,w) grid
        positions, text continues 1D from the grid edge."""
        cfg = self.cfg
        b, s = tokens.shape
        gh, gw = cfg.patch_grid
        npatch = cfg.n_patches
        idx = jnp.arange(s)
        is_text = idx >= npatch
        t = jnp.where(is_text, idx - npatch + max(gh, gw), 0)
        h = jnp.where(is_text, t, idx // max(gw, 1))
        w = jnp.where(is_text, t, idx % max(gw, 1))
        pos3 = jnp.stack([t, h, w])                    # [3,S]
        return jnp.broadcast_to(pos3[:, None, :], (3, b, s))

    def _backbone(self, p: Params, x: jnp.ndarray, batch: Batch
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Token embeddings -> final hidden states.  Returns (h, aux)."""
        cfg, feats = self.cfg, self.features
        aux = jnp.zeros((), jnp.float32)
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            pos3 = (self._vlm_positions3(batch["tokens"])
                    if fam == "vlm" else None)
            x, aux = tf_mod.apply_stack(
                p["blocks"], x, cfg.block_config(), feats,
                rules=self.rules, mesh=self.mesh, positions3=pos3)
        elif fam == "xlstm":
            xc = cfg.xlstm_config()

            def pair(layer_p, h):
                h = xlstm_mod.apply_mlstm_block(layer_p["m"], h, xc)
                h = xlstm_mod.apply_slstm_block(layer_p["s"], h, xc)
                return h, jnp.zeros((), jnp.float32)

            stacked = {"m": p["mlstm"], "s": p["slstm"]}
            x, aux = _scan_stack_generic(stacked, x, pair, feats)
        elif fam == "hybrid":
            x, aux = self._hybrid_backbone(p, x)
        elif fam == "encdec":
            x = self._encdec_backbone(p, x, batch)
        return x, aux

    def _hybrid_backbone(self, p: Params, x: jnp.ndarray):
        cfg, feats = self.cfg, self.features
        mc = cfg.mamba_config()
        bc = cfg.block_config()

        def mamba_one(layer_p, h):
            return ssm_mod.apply_mamba2_block(layer_p, h, mc), \
                jnp.zeros((), jnp.float32)

        aux = jnp.zeros((), jnp.float32)
        for lo, hi in _hybrid_groups(cfg.n_layers, cfg.attn_every):
            seg = jax.tree.map(lambda a: a[lo:hi], p["mamba"])
            x, a = _scan_stack_generic(seg, x, mamba_one, feats)
            aux = aux + a
            x, a2 = tf_mod.apply_block(p["shared_attn"], x, bc,
                                       rules=self.rules, mesh=self.mesh)
            aux = aux + a2
        return x, aux

    def _encdec_backbone(self, p: Params, x: jnp.ndarray, batch: Batch):
        cfg, feats = self.cfg, self.features
        ec = cfg.encdec_config()
        enc_cfg = ec._replace(attn=ec.attn._replace(causal=False))
        src = batch["src_embeds"].astype(self.dtype)

        def enc_one(layer_p, h):
            return encdec_mod.apply_encoder_block(layer_p, h, enc_cfg), \
                jnp.zeros((), jnp.float32)

        mem, _ = _scan_stack_generic(p["encoder"], src, enc_one, feats)
        mem = layer_norm(mem, p["enc_final_norm"], cfg.norm_eps)

        def dec_one(layer_p, h):
            mk, mv = encdec_mod.cross_memory(layer_p["cross"], mem, ec.attn)
            return encdec_mod.apply_decoder_block(layer_p, h, mk, mv, ec), \
                jnp.zeros((), jnp.float32)

        x, _ = _scan_stack_generic(p["decoder"], x, dec_one, feats)
        return x

    # ============================================================== train
    def forward(self, p: Params, batch: Batch) -> jnp.ndarray:
        x = self._embed(p, batch["tokens"], batch.get("patch_embeds"))
        x = constrain(x, ("batch", "act_seq", "act_embed"),
                      self.rules, self.mesh)
        h, _ = self._backbone(p, x, batch)
        return self._head(p, h)

    def loss(self, p: Params, batch: Batch
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        x = self._embed(p, batch["tokens"], batch.get("patch_embeds"))
        x = constrain(x, ("batch", "act_seq", "act_embed"),
                      self.rules, self.mesh)
        h, aux = self._backbone(p, x, batch)
        logits = self._head(p, h)
        labels = batch["labels"]
        weights = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * weights
        ntok = jnp.maximum(jnp.sum(weights), 1.0)
        ce = jnp.sum(nll) / ntok
        total = ce + aux
        return total, {"ce": ce, "aux": aux, "ntok": ntok}

    # ============================================================== serve
    def init_decode_state(self, batch_size: int, max_seq: int,
                          page_size: int = 0,
                          num_pages: Optional[int] = None,
                          table_width: Optional[int] = None,
                          kv_dtype=None) -> Any:
        """Fresh decode state.  ``page_size > 0`` builds PAGED KV caches
        (attention-cache families only): a pool of ``num_pages`` pages of
        ``page_size`` tokens shared by all rows, addressed through per-row
        page tables of ``table_width`` logical pages (defaults provision
        the dense worst case — callers that know their traffic pass a
        smaller pool, which is the whole point).  ``kv_dtype`` overrides
        the page storage dtype (``jnp.int8`` = quantized pages with
        per-token scales; paged caches only)."""
        cfg = self.cfg
        fam = cfg.family
        ac = cfg.attn_config()
        if page_size > 0 and fam not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"paged KV caches need an attention-cache family, not {fam!r}"
                " (recurrent states have no pages to swap)")
        if kv_dtype is not None and page_size <= 0:
            raise ValueError("kv_dtype needs a paged KV cache "
                             "(page_size > 0)")
        if fam in ("dense", "moe", "vlm"):
            if page_size > 0:
                nppr = -(-max_seq // page_size)
                cache = attn_mod.init_paged_kv_cache(
                    batch_size, num_pages or batch_size * nppr + 1,
                    table_width or nppr, page_size, ac, self.dtype,
                    kv_dtype=kv_dtype)
            else:
                cache = attn_mod.init_kv_cache(batch_size, max_seq, ac,
                                               self.dtype)
            state = {"caches": _stack_tree(cache, cfg.n_layers)}
            if fam == "moe":
                state[MOE_COUNTS] = jnp.zeros((len(MOE_KINDS), len(COUNTS)),
                                              jnp.int32)
            return state
        if fam == "xlstm":
            xc = cfg.xlstm_config()
            n_pairs = cfg.n_layers // 2
            return {
                "mlstm": _stack_tree(
                    xlstm_mod.init_mlstm_state(batch_size, xc), n_pairs),
                "slstm": _stack_tree(
                    xlstm_mod.init_slstm_state(batch_size, xc), n_pairs),
            }
        if fam == "hybrid":
            mc = cfg.mamba_config()
            n_groups = len(_hybrid_groups(cfg.n_layers, cfg.attn_every))
            return {
                "mamba": _stack_tree(
                    ssm_mod.init_mamba2_state(batch_size, mc), cfg.n_layers),
                "attn_caches": _stack_tree(
                    attn_mod.init_kv_cache(batch_size, max_seq, ac,
                                           self.dtype), n_groups),
            }
        if fam == "encdec":
            cache = attn_mod.init_kv_cache(batch_size, max_seq, ac, self.dtype)
            s_src = max(max_seq // cfg.src_ratio, 1)
            kvh, dh = cfg.num_kv_heads, cfg.resolved_head_dim
            mem = jnp.zeros((cfg.n_layers, batch_size, s_src, kvh, dh),
                            self.dtype)
            return {"caches": _stack_tree(cache, cfg.n_layers),
                    "mem_k": mem, "mem_v": mem}
        raise ValueError(fam)

    def state_specs(self, state: Any) -> Any:
        """Logical axes for the decode state (caches shard seq over data)."""
        def leaf_spec(path_leaf):
            return None
        # Cache tensors: [L, B, S, KVH, Dh]; recurrent states [L, B, H, ...]
        def spec_for(x):
            if x.ndim == 5:
                return ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
            if x.ndim == 4:
                return ("layers", "batch", "heads", None)
            if x.ndim == 3:
                return ("layers", "batch", None)
            if x.ndim == 2:                 # stacked per-row cache lengths
                return ("layers", "batch")
            return tuple([None] * x.ndim)
        return jax.tree.map(spec_for, state)

    def prefill(self, p: Params, batch: Batch, state: Any,
                all_logits: bool = False) -> Tuple[jnp.ndarray, Any]:
        """Process the prompt; returns (last-token logits [B,V], state).

        ``batch["lengths"]`` [B] (optional) marks each row's true prompt
        length inside right-padded ``tokens``: attention-cache families mask
        pad keys out of every softmax, record per-row cache lengths, and the
        returned logits are each row's LAST REAL token's — ragged prompts
        batch exactly.  Recurrent-state families (xlstm, hybrid) cannot
        mask a pad out of an already-updated running state, so they keep the
        equal-length-wave semantics (serve equal lengths, or admit rows one
        at a time through the continuous-batching scheduler, which prefills
        each prompt at its exact length).

        ``all_logits=True`` returns the full per-position head ``[B,S,V]``
        instead of the last-token gather — the multi-token verify gather of
        speculative decoding (every suffix position's next-token
        distribution from ONE forward pass).
        """
        cfg, feats = self.cfg, self.features
        tokens = batch["tokens"]
        lengths = batch.get("lengths")
        prefix_len = batch.get("prefix_len")   # [B]: resident shared prefix
        x = self._embed(p, tokens, batch.get("patch_embeds"))
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            bc = cfg.block_config()
            pos3 = (self._vlm_positions3(tokens) if fam == "vlm" else None)
            x, new_caches, counts = tf_mod.apply_stack_decode(
                p["blocks"], x, bc, state["caches"], feats,
                rules=self.rules, mesh=self.mesh, positions3=pos3,
                block_fn=functools.partial(tf_mod.apply_block_prefill,
                                           lengths=lengths,
                                           prefix_len=prefix_len))
            new_state = _counted(dict(state, caches=new_caches), "prefill",
                                 counts)
        elif fam == "xlstm":
            xc = cfg.xlstm_config()

            def pair(h, scanned):
                layer_p, st = scanned
                h, m_st = xlstm_mod.apply_mlstm_block(
                    layer_p["m"], h, xc, initial_state=st["m"],
                    return_state=True)
                h, s_st = xlstm_mod.apply_slstm_block(
                    layer_p["s"], h, xc, initial_state=st["s"],
                    return_state=True)
                return h, {"m": m_st, "s": s_st}

            stacked = {"m": p["mlstm"], "s": p["slstm"]}
            st0 = {"m": state["mlstm"], "s": state["slstm"]}
            x, new_st = _scan_stack_state(stacked, st0, x, pair, feats)
            new_state = {"mlstm": new_st["m"], "slstm": new_st["s"]}
        elif fam == "hybrid":
            x, new_state = self._hybrid_prefill(p, x, state)
        elif fam == "encdec":
            x, new_state = self._encdec_prefill(p, x, batch, state)
        if all_logits:
            return self._head(p, x), new_state
        if lengths is not None and fam in ("dense", "moe", "vlm"):
            # per-row last REAL token (pads are masked context, not input)
            idx = jnp.maximum(lengths - 1, 0)[:, None, None]
            x_last = jnp.take_along_axis(x, idx, axis=1)
        else:
            x_last = x[:, -1:]
        logits = self._head(p, x_last)[:, 0]
        return logits, new_state

    def _hybrid_prefill(self, p, x, state):
        cfg, feats = self.cfg, self.features
        mc, bc = cfg.mamba_config(), cfg.block_config()
        groups = _hybrid_groups(cfg.n_layers, cfg.attn_every)
        new_mamba, new_attn = [], []

        def mamba_one(h, scanned):
            layer_p, st = scanned
            h, new = ssm_mod.apply_mamba2_block(layer_p, h, mc,
                                                initial_state=st,
                                                return_state=True)
            return h, new

        for gi, (lo, hi) in enumerate(groups):
            seg_p = jax.tree.map(lambda a: a[lo:hi], p["mamba"])
            seg_st = jax.tree.map(lambda a: a[lo:hi], state["mamba"])
            x, seg_new = _scan_stack_state_pair(seg_p, seg_st, x, mamba_one,
                                                feats)
            new_mamba.append(seg_new)
            cache_g = jax.tree.map(lambda a: a[gi], state["attn_caches"])
            x, new_c, _ = tf_mod.apply_block_prefill(
                p["shared_attn"], x, bc, KVCache(*cache_g)
                if not isinstance(cache_g, KVCache) else cache_g,
                rules=self.rules, mesh=self.mesh)
            new_attn.append(new_c)
        mamba_state = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_mamba)
        attn_state = jax.tree.map(lambda *xs: jnp.stack(xs), *new_attn)
        return x, {"mamba": mamba_state, "attn_caches": attn_state}

    def _encdec_prefill(self, p, x, batch, state):
        cfg, feats = self.cfg, self.features
        ec = cfg.encdec_config()
        enc_cfg = ec._replace(attn=ec.attn._replace(causal=False))
        src = batch["src_embeds"].astype(self.dtype)

        def enc_one(layer_p, h):
            return encdec_mod.apply_encoder_block(layer_p, h, enc_cfg), \
                jnp.zeros((), jnp.float32)

        mem, _ = _scan_stack_generic(p["encoder"], src, enc_one, feats)
        mem = layer_norm(mem, p["enc_final_norm"], cfg.norm_eps)

        # per-layer cross K/V memory
        def mk_mem(layer_p):
            return encdec_mod.cross_memory(layer_p["cross"], mem, ec.attn)
        mem_kv = jax.vmap(mk_mem)(p["decoder"])       # ([L,B,S,H,D], ...)

        def dec_one(h, scanned):
            layer_p, (cache, mk, mv) = scanned
            a, new_cache = attn_mod.prefill_into_cache(
                layer_p["attn"],
                layer_norm(h, layer_p["ln1"], ec.norm_eps), ec.attn, cache)
            h = h + a
            h = h + encdec_mod._cross_attend(
                layer_p["cross"],
                layer_norm(h, layer_p["ln_cross"], ec.norm_eps),
                mk, mv, ec.attn)
            from repro.models.layers import gelu_mlp
            m = gelu_mlp(layer_norm(h, layer_p["ln2"], ec.norm_eps),
                         layer_p["mlp"]["w_up"].astype(h.dtype),
                         layer_p["mlp"]["b_up"].astype(h.dtype),
                         layer_p["mlp"]["w_down"].astype(h.dtype),
                         layer_p["mlp"]["b_down"].astype(h.dtype))
            return h + m, new_cache

        def body(h, scanned):
            return dec_one(h, scanned)

        x, new_caches = jax.lax.scan(
            body, x, (p["decoder"], (state["caches"], *mem_kv)))
        return x, {"caches": new_caches,
                   "mem_k": mem_kv[0].astype(self.dtype),
                   "mem_v": mem_kv[1].astype(self.dtype)}

    def decode_step(self, p: Params, tokens: jnp.ndarray, state: Any
                    ) -> Tuple[jnp.ndarray, Any]:
        """tokens: [B,1] -> (logits [B,V], new state)."""
        cfg, feats = self.cfg, self.features
        x = self._embed(p, tokens)
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            bc = cfg.block_config()
            x, new_caches, counts = tf_mod.apply_stack_decode(
                p["blocks"], x, bc, state["caches"], feats,
                rules=self.rules, mesh=self.mesh)
            new_state = _counted(dict(state, caches=new_caches), "decode",
                                 counts)
        elif fam == "xlstm":
            xc = cfg.xlstm_config()

            def pair(h, scanned):
                layer_p, st = scanned
                h, m_st = xlstm_mod.mlstm_decode(layer_p["m"], h, xc, st["m"])
                h, s_st = xlstm_mod.slstm_decode(layer_p["s"], h, xc, st["s"])
                return h, {"m": m_st, "s": s_st}

            stacked = {"m": p["mlstm"], "s": p["slstm"]}
            st0 = {"m": state["mlstm"], "s": state["slstm"]}
            x, new_st = _scan_stack_state(stacked, st0, x, pair, feats)
            new_state = {"mlstm": new_st["m"], "slstm": new_st["s"]}
        elif fam == "hybrid":
            mc, bc = cfg.mamba_config(), cfg.block_config()
            groups = _hybrid_groups(cfg.n_layers, cfg.attn_every)
            new_mamba, new_attn = [], []

            def mamba_one(h, scanned):
                layer_p, st = scanned
                return ssm_mod.mamba2_decode(layer_p, h, mc, st)

            for gi, (lo, hi) in enumerate(groups):
                seg_p = jax.tree.map(lambda a: a[lo:hi], p["mamba"])
                seg_st = jax.tree.map(lambda a: a[lo:hi], state["mamba"])
                x, seg_new = _scan_stack_state_pair(seg_p, seg_st, x,
                                                    mamba_one, feats)
                new_mamba.append(seg_new)
                cache_g = jax.tree.map(lambda a: a[gi], state["attn_caches"])
                cache_g = KVCache(*cache_g) if not isinstance(cache_g, KVCache) else cache_g
                x, new_c, _ = tf_mod.apply_block_decode(
                    p["shared_attn"], x, bc, cache_g,
                    rules=self.rules, mesh=self.mesh)
                new_attn.append(new_c)
            new_state = {
                "mamba": jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *new_mamba),
                "attn_caches": jax.tree.map(
                    lambda *xs: jnp.stack(xs), *new_attn),
            }
        elif fam == "encdec":
            ec = cfg.encdec_config()

            def dec_one(h, scanned):
                layer_p, (cache, mk, mv) = scanned
                return encdec_mod.apply_decoder_block_decode(
                    layer_p, h, mk, mv, cache, ec)

            x, new_caches = jax.lax.scan(
                dec_one, x,
                (p["decoder"], (state["caches"], state["mem_k"],
                                state["mem_v"])))
            new_state = dict(state, caches=new_caches)
        else:
            raise ValueError(fam)
        logits = self._head(p, x)[:, 0]
        return logits, new_state

    # ============================================================== sizes
    def num_params(self) -> int:
        shapes = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    def num_active_params(self) -> int:
        """Per-token active params (MoE: routed top-k only)."""
        n = self.num_params()
        cfg = self.cfg
        if cfg.family != "moe":
            return n
        mc = cfg.moe_config()
        per_layer_all = (3 * cfg.d_model * cfg.d_ff * mc.held
                         + cfg.d_model * cfg.moe_experts)
        n_dense = n - cfg.n_layers * per_layer_all
        return n_dense + cfg.n_layers * count_active_params(mc)


# ---------------------------------------------------------------------------
# scan helpers
# ---------------------------------------------------------------------------

def _counted(state, kind: str, counts):
    """``state`` with ``counts`` (None for a dense MLP) added to its
    ``kind`` row of :data:`MOE_COUNTS`, where it has one."""
    if counts is None or MOE_COUNTS not in state:
        return state
    row = MOE_KINDS.index(kind)
    return dict(state, **{MOE_COUNTS: state[MOE_COUNTS].at[row].add(counts)})


def _stack_tree(tree, n: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)


def _hybrid_groups(n_layers: int, every: int):
    out = []
    lo = 0
    while lo < n_layers:
        out.append((lo, min(lo + every, n_layers)))
        lo += every
    return out


def _scan_stack_generic(stacked, x, block_fn, features: FeatureSet):
    """Scan stacked params with (params, x) -> (y, aux) blocks + remat."""
    one = block_fn
    policy = tf_mod.remat_policy_fn(features)
    if features.remat_policy != "none":
        one = jax.checkpoint(one, policy=policy)
    if features.scan_layers:
        def body(carry, layer_p):
            h, aux = carry
            y, a = one(layer_p, h)
            return (y, aux + a), None
        (y, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   stacked, unroll=features.scan_unroll)
        return y, aux
    n = jax.tree.leaves(stacked)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    for i in range(n):
        layer_p = jax.tree.map(lambda a: a[i], stacked)
        x, a = one(layer_p, x)
        aux = aux + a
    return x, aux


def _scan_stack_state(stacked, states, x, block_fn, features: FeatureSet):
    """Scan with per-layer state threading: (x, (params, state)) -> (y, new)."""
    if features.scan_layers:
        y, new_states = jax.lax.scan(block_fn, x, (stacked, states))
        return y, new_states
    n = jax.tree.leaves(stacked)[0].shape[0]
    outs = []
    for i in range(n):
        layer_p = jax.tree.map(lambda a: a[i], stacked)
        layer_s = jax.tree.map(lambda a: a[i], states)
        x, ns = block_fn(x, (layer_p, layer_s))
        outs.append(ns)
    new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, new_states


# alias — same mechanics, used where params/state travel as a pair
_scan_stack_state_pair = _scan_stack_state
