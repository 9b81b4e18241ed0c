"""The Qwen3-MoE family: a Qwen3-MoE decoder as the program serves it, one
chip's share of its experts, its seeded weights, and its plain reference.

The configuration file states the published ``config.json`` keys, with
``num_experts`` the experts this chip holds (``expert_offset`` the first
of them) and the published router width under ``reduced``.  The benchmark
reaches the model only through this module (``engine``, ``make_weights``,
``widest_gap``, ``layer_matmul_params``, ``kv_bytes_per_token``, as for
``families/qwen2.py``), and the MoE readers through ``gmm_work``.

Weights.  The program's layout (``blocks`` stacked over layers, the
experts held here as ``moe.w_gate``/``w_up`` ``[held, d, f]`` and
``w_down`` ``[held, f, d]``, the router ``[d, routed]``), bf16, drawn by
one jitted call from the seed: every matrix N(0, ``initializer_range``),
every norm scale (q_norm and k_norm included) 1.

Reference.  Straightforward ``jax.numpy`` in float32 at ``highest``,
calling nothing of the program: embed; per layer RMSNorm, q/k/v
projections without bias, RMSNorm over head_dim on each query and key
head (QK-norm), 1-D rotary embeddings (rotate-half), causal GQA, the
output projection and a residual; then RMSNorm, the router's softmax over
every routed expert, its top-k renormalised to sum to 1, and for each
expert held here in turn (a plain loop over the held experts, no
sorting, no grouping) its SwiGLU FFN over every position, weighted by its
gate where the position routed to it and 0 elsewhere; pairs routed to
experts not held add nothing, as in the program; a residual; final
RMSNorm and the untied head.  The RMSNorm, RoPE, fp8 rounding and head are
``families/qwen2.py``'s.  ``control=True`` computes every matrix product,
the router's included, in scaled fp8 (e4m3), as that family's control
does.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.features import default_features
from repro.models.lm import LM, LMConfig
from repro.serve import Engine, ServeConfig

_spec = importlib.util.spec_from_file_location(
    "_qwen2_for_qwen3_moe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "qwen2.py"))
_q2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_q2)

head_dim, kv_bytes_per_token = _q2.head_dim, _q2.kv_bytes_per_token
serve_config = _q2.serve_config


# ------------------------------------------------------------ the program
def routed_experts(cfg: Dict) -> int:
    """The router's width: the published expert count."""
    return int(cfg["reduced"].get("num_experts", cfg["num_experts"]))


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer: the q, k, v and
    o projections, the router, and the experts held here that it is
    expected to reach (top-k x held / routed of one expert's three
    matrices)."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e = routed_experts(cfg)
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] / e
    return int(d * dh * (2 * h + 2 * kvh) + d * e
               + held * 3 * d * cfg["moe_intermediate_size"])


def expert_bytes(cfg: Dict) -> int:
    """One expert's three matrices, bf16."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2


def gmm_work(cfg: Dict, held_rows: float, loads: float):
    """(FLOPs, bytes) of the grouped expert matmul's three calls for
    ``held_rows`` (token, expert) pairs computed here and ``loads`` held
    experts touched (each over a layer and a program call): each touched
    expert's three matrices read once, each row's input (bf16) and output
    (f32) of the gate, up and down products, 2 x 3 x d x f FLOPs a row."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 6.0 * d * f * held_rows
    rows = (2 * d * 2 + 2 * f * 4) + (f * 2 + d * 4)
    return flops, float(loads) * expert_bytes(cfg) + float(held_rows) * rows


def lm_config(cfg: Dict) -> LMConfig:
    """The program's config for ``cfg``: the repository's own entry for
    the architecture at the file's depth, holding the file's experts, and
    held to every published width the file states."""
    base = get_arch(cfg["arch_id"]).config
    lc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"],
        moe_first_expert=cfg["expert_offset"],
        moe_held_experts=cfg["num_experts"], **cfg.get("program", {}))
    want = {
        "family": "moe", "d_model": cfg["hidden_size"],
        "d_ff": cfg["moe_intermediate_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "vocab": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "qkv_bias": cfg["attention_bias"], "qk_norm": True,
        "moe_experts": routed_experts(cfg),
        "moe_top_k": cfg["num_experts_per_tok"], "moe_shared_experts": 0,
    }
    got = {k: getattr(lc, k) for k in want}
    dh = head_dim(cfg)
    if (got != want or lc.resolved_head_dim != dh or lc.norm != "rmsnorm"
            or not cfg["norm_topk_prob"]
            or cfg["expert_offset"] + cfg["num_experts"]
            > routed_experts(cfg)):
        raise ValueError(f"{cfg['name']}: the program's config {got} "
                         f"(head dim {lc.resolved_head_dim}) is not the "
                         f"file's {want} (head dim {dh}, experts "
                         f"{cfg['expert_offset']}+{cfg['num_experts']})")
    return lc


def _lm(cfg: Dict) -> LM:
    return LM(lm_config(cfg), default_features().with_(remat_policy="none"),
              dtype=jnp.bfloat16)


def engine(cfg: Dict, params, mesh=None,
           scfg: Optional[ServeConfig] = None) -> Engine:
    return Engine(_lm(cfg), params, scfg or serve_config(cfg), mesh=mesh)


# ---------------------------------------------------------------- weights
def shapes(cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    h, kvh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    f, v, held = (cfg["moe_intermediate_size"], cfg["vocab_size"],
                  cfg["num_experts"])
    layer = {
        "ln1": {"scale": (d,)},
        "attn": {"wq": (d, h, dh), "wk": (d, kvh, dh), "wv": (d, kvh, dh),
                 "wo": (h, dh, d), "q_norm": {"scale": (dh,)},
                 "k_norm": {"scale": (dh,)}},
        "ln2": {"scale": (d,)},
        "moe": {"router": (d, routed_experts(cfg)),
                "w_gate": (held, d, f), "w_up": (held, d, f),
                "w_down": (held, f, d)},
    }
    rest = {"embed": {"table": (v, d)}, "final_norm": {"scale": (d,)},
            "lm_head": {"w": (d, v)}}
    return {"layer": layer, "rest": rest}


def make_weights(cfg: Dict, seed: int, mesh=None, dtype=jnp.bfloat16):
    """The whole tree from ``seed``, a layer at a time inside one program
    (``lax.map``), on the default device or into the program's shardings
    over ``mesh``."""
    lm = _lm(cfg)                 # refuses a program that cannot serve it
    sh = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def build(key):
        k_rest, k_layers = jax.random.split(key)
        rest = _q2._fill(k_rest, sh["rest"], std, dtype)
        blocks = jax.lax.map(lambda k: _q2._fill(k, sh["layer"], std, dtype),
                             jax.random.split(k_layers,
                                              cfg["num_hidden_layers"]))
        return dict(rest, blocks=blocks)

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    if mesh is None:
        return jax.block_until_ready(jax.jit(build)(key))
    from jax.sharding import NamedSharding
    specs = lm.param_pspecs(mesh.mesh, jax.eval_shape(build, key))
    out = jax.tree.map(lambda s: NamedSharding(mesh.mesh, s), specs)
    return jax.block_until_ready(jax.jit(build, out_shardings=out)(key))


# -------------------------------------------------------------- reference
_rms, _rope, _q8, _mm = _q2._rms, _q2._rope, _q2._q8, _q2._mm


def _attention(x, p, cfg, low):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    h = _rms(x, f32(p["ln1"]["scale"]), eps)
    a = p["attn"]
    q = _mm(h, f32(a["wq"]), "sd,dhk->shk", low, (0,))
    k = _mm(h, f32(a["wk"]), "sd,dhk->shk", low, (0,))
    v = _mm(h, f32(a["wv"]), "sd,dhk->shk", low, (0,))
    q = _rms(q, f32(a["q_norm"]["scale"]), eps)
    k = _rms(k, f32(a["k_norm"]["scale"]), eps)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    nh, kvh, dh = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(s, kvh, nh // kvh, dh)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k) / np.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(s, nh, dh)
    if low:
        o, wo = _q8(o, (1, 2)), _q8(f32(a["wo"]), (0, 1))
    else:
        wo = f32(a["wo"])
    return x + jnp.einsum("shk,hkd->sd", o, wo)


def _experts(x, p, cfg, low):
    """The MoE half: router over every routed expert, then each held
    expert's FFN over every position, weighted where routed to it."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rms(x, f32(p["ln2"]["scale"]), cfg["rms_norm_eps"])
    m = p["moe"]
    probs = jax.nn.softmax(_mm(h, f32(m["router"]), "sd,de->se", low, (0,)),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    held = m["w_gate"].shape[0]
    mine = jnp.arange(held) + cfg["expert_offset"]             # [H]
    gate = jnp.sum(jnp.where(idx[:, :, None] == mine, top[:, :, None], 0.0),
                   axis=1)                                      # [S, H]

    def one(y, e):
        wg, wu, wd, g = e
        ffn = _mm(jax.nn.silu(_mm(h, f32(wg), "sd,df->sf", low, (0,)))
                  * _mm(h, f32(wu), "sd,df->sf", low, (0,)),
                  f32(wd), "sf,fd->sd", low, (0,))
        return y + g[:, None] * ffn, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (m["w_gate"], m["w_up"], m["w_down"], gate.T))
    return x + y


def _hidden(params, tokens, cfg, low):
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)

        def body(x, p):
            return _experts(_attention(x, p, cfg, low), p, cfg, low), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return _rms(x, params["final_norm"]["scale"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "control", "width"))
def _gap_program(params, tokens, start, cfg_items, control, width):
    """As ``families/qwen2.py``'s: the reference's best logit minus its
    logit of the token chosen after each of ``width`` positions from
    ``start`` on (the served one, or under ``control`` the fp8 path's
    first choice)."""
    cfg = dict(cfg_items)
    pick = lambda x: jax.lax.dynamic_slice_in_dim(x, start, width)  # noqa
    ref = _q2._head(params, pick(_hidden(params, tokens, cfg, False)), cfg,
                    False)
    if control:
        chosen = jnp.argmax(_q2._head(params, pick(_hidden(
            params, tokens, cfg, True)), cfg, True), -1)
    else:
        chosen = pick(jnp.roll(tokens, -1))
    return ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]


_KEYS = ("rms_norm_eps", "rope_theta", "tie_word_embeddings",
         "num_experts_per_tok", "expert_offset")


def gaps(params, cfg: Dict, seq: Sequence[int], start: int, max_seq: int,
         width: int, control: bool = False) -> np.ndarray:
    """``families/qwen2.py``'s ``gaps`` through this family's reference."""
    n = len(seq) - start
    if not 0 < n <= width <= max_seq:
        raise ValueError(f"{n} served tokens, width {width}")
    items = tuple((k, cfg[k]) for k in _KEYS)
    toks = np.zeros(max_seq, np.int32)
    toks[:len(seq)] = seq
    s0 = min(start - 1, max_seq - width)
    g = np.asarray(_gap_program(params, jnp.asarray(toks), s0, items,
                                control, width))
    return g[start - 1 - s0:start - 1 - s0 + n]


def widest_gap(params, cfg: Dict, served: Sequence[Tuple[Sequence[int],
                                                       Sequence[int]]],
               max_seq: int, width: int, control: bool = False) -> float:
    worst = 0.0
    for prompt, out in served:
        g = gaps(params, cfg, list(prompt) + list(out), len(prompt),
                 max_seq, width, control)
        worst = max(worst, float(g.max()))
    return worst
