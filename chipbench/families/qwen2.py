"""The Qwen2 family: a Qwen2-style decoder as the program serves it, its
seeded weights, and its plain reference.

A configuration names its family (``"family": "qwen2"``) and the
benchmark reaches the model only through this module:

* ``engine(cfg, params, mesh)``: the program's ``LM`` and ``Engine`` over
  the paged ``KVPool``, with the registry's default kernels, sharded over
  ``mesh`` (a ``repro.launch.mesh.ServeMesh``) when one is given;
* ``make_weights(cfg, seed, mesh)``: the weights, made by the benchmark;
* ``widest_gap(params, cfg, served, max_seq, width, control)``: the
  comparison with the plain reference that decides ``correct``;
* ``layer_matmul_params(cfg)`` and ``kv_bytes_per_token(cfg)``: the counts
  that ``work.py`` takes from the family.

Weights.  The tree has the layout the serving program reads
(``embed.table``, ``final_norm.scale``, ``blocks`` stacked over layers,
``lm_head.w`` when the head is untied), in the dtype it is served in,
built by one jitted call from the seed.  Every matrix and bias is N(0,
std) with std the configuration's ``initializer_range`` (0.02 in Qwen2's
published configs); norm scales are 1.  Biases are drawn too, so that a
fault on the bias path moves the logits.  With std 0.02 the residual
stream is carried by the layers and not by the input embedding, so greedy
decoding of these weights does not fall into repeating its input token:
the tokens depend on the attention over the context, which is what the
output comparison has to see.  On a mesh every leaf is drawn straight into
the sharding the program's own rules give it, so no chip holds the whole
tree.

Reference.  Straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision, with no kernel, cache or batching: embed, then per
layer RMSNorm, GQA attention with 1-D rotary embeddings (rotate-half
form, as published for Qwen2; Qwen2-VL's M-RoPE gives text tokens equal
temporal, height and width positions and so reduces to the same rotation)
and QKV bias, then RMSNorm and a SwiGLU MLP, each with a residual; final
RMSNorm and the head (tied to the embedding or not).  It calls nothing of
the program and reads the weights made here; on a mesh it runs on the
same sharded tree, each layer's weights cast to float32 inside the scan
over layers, one layer at a time.  One program covers every request: the
sequence (prompt plus served tokens) is padded at its end to ``max_seq``,
which causal attention keeps from every real position.

``control=True`` is the control.  Every matrix product takes its weights
scaled per output channel and its activations per token into fp8 (e4m3,
round to nearest, the scale putting each row's largest magnitude at the
format's largest), the next precision below the served bf16, as fp8
serving computes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.features import default_features
from repro.models.lm import LM, LMConfig
from repro.serve import Engine, ServeConfig


# ------------------------------------------------------------ the program
def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one token over every layer, bf16."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * 2)


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer: the q, k, v and
    o projections and the SwiGLU MLP."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * dh * (2 * h + 2 * kvh) + 3 * d * cfg["intermediate_size"]


def lm_config(cfg: Dict) -> LMConfig:
    """The program's config for ``cfg``: the repository's own entry for
    the architecture, its depth set to the file's, and held to every
    published width the file states."""
    base = get_arch(cfg["arch_id"]).config
    over = dict(cfg.get("program", {}))
    if "mrope_sections" in over:
        over["mrope_sections"] = tuple(over["mrope_sections"])
    lc = dataclasses.replace(base, n_layers=cfg["num_hidden_layers"], **over)
    want = {
        "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "vocab": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": cfg["tie_word_embeddings"], "qkv_bias": True,
    }
    got = {k: getattr(lc, k) for k in want}
    dh = head_dim(cfg)
    if got != want or lc.resolved_head_dim != dh or lc.norm != "rmsnorm":
        raise ValueError(f"{cfg['name']}: the program's config {got} "
                         f"(head dim {lc.resolved_head_dim}) is not the "
                         f"file's {want} (head dim {dh})")
    return lc


def serve_config(cfg: Dict) -> ServeConfig:
    s = cfg["serve"]
    pool = None
    if s.get("pool_gib"):
        page = s["page_size"] * kv_bytes_per_token(cfg)
        pool = int(s["pool_gib"] * 2**30 // page)
    return ServeConfig(page_size=s["page_size"], max_seq=s["max_seq"],
                       batch_slots=s["batch_slots"], pool_pages=pool,
                       temperature=0.0, eos_token=-1, prefix_cache=True)


def _lm(cfg: Dict) -> LM:
    return LM(lm_config(cfg), default_features().with_(remat_policy="none"),
              dtype=jnp.bfloat16)


def engine(cfg: Dict, params, mesh=None,
           scfg: Optional[ServeConfig] = None) -> Engine:
    """The serving engine over ``params``, sharded over ``mesh`` (a
    ``ServeMesh``) when one is given."""
    return Engine(_lm(cfg), params, scfg or serve_config(cfg), mesh=mesh)


# ---------------------------------------------------------------- weights
def shapes(cfg: Dict) -> Dict:
    """Leaf shapes of one layer and of the rest, from the config file's
    published keys."""
    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = head_dim(cfg)
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    layer = {
        "ln1": {"scale": (d,)},
        "attn": {"wq": (d, h, dh), "wk": (d, kvh, dh), "wv": (d, kvh, dh),
                 "wo": (h, dh, d), "bq": (h, dh), "bk": (kvh, dh),
                 "bv": (kvh, dh)},
        "ln2": {"scale": (d,)},
        "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
    }
    rest = {"embed": {"table": (v, d)}, "final_norm": {"scale": (d,)}}
    if not cfg["tie_word_embeddings"]:
        rest["lm_head"] = {"w": (d, v)}
    return {"layer": layer, "rest": rest}


def _fill(key, tree, std, dtype):
    paths = _paths(tree)
    out: Dict = {}
    for k, (path, shape) in zip(jax.random.split(key, len(paths)), paths):
        *parents, leaf = path.strip("/").split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = (jnp.ones(shape, dtype) if leaf == "scale" else
                      (jax.random.normal(k, shape, dtype) * std).astype(dtype))
    return out


def _paths(tree, prefix=""):
    if isinstance(tree, tuple):
        return [(prefix, tree)]
    out = []
    for name in sorted(tree):
        out.extend(_paths(tree[name], f"{prefix}/{name}"))
    return out


def make_weights(cfg: Dict, seed: int, mesh=None, dtype=jnp.bfloat16):
    """The whole tree from ``seed``: on the default device, or with
    ``mesh`` (a ``ServeMesh``) each leaf in the sharding the program's
    rules give it.  Layers are drawn one at a time inside the program
    (``lax.map``), so the draw needs the memory of one layer's random bits
    and no more."""
    sh = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    n = cfg["num_hidden_layers"]

    def build(key):
        k_rest, k_layers = jax.random.split(key)
        rest = _fill(k_rest, sh["rest"], std, dtype)
        blocks = jax.lax.map(lambda k: _fill(k, sh["layer"], std, dtype),
                             jax.random.split(k_layers, n))
        return dict(rest, blocks=blocks)

    # seeds run past 32 bits: fold the high word in
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    if mesh is None:
        return jax.block_until_ready(jax.jit(build)(key))
    from jax.sharding import NamedSharding
    specs = _lm(cfg).param_pspecs(mesh.mesh, jax.eval_shape(build, key))
    out = jax.tree.map(lambda s: NamedSharding(mesh.mesh, s), specs)
    return jax.block_until_ready(jax.jit(build, out_shardings=out)(key))


# -------------------------------------------------------------- reference
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def _q8(x, axis):
    """Scaled fp8 (e4m3) round trip of ``x``, one scale per slice along
    the reduction ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, spec, low, w_axes):
    """einsum ``spec`` of activations ``x`` (tokens first) and weights
    ``w``; under ``low`` both go through fp8 first (activations per token,
    weights per output channel: ``w_axes`` are the reduction axes)."""
    if low:
        x = _q8(x, tuple(range(1, x.ndim)))
        w = _q8(w, w_axes)
    return jnp.einsum(spec, x, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, cfg, low):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = x.shape[0]
    h = _rms(x, f32(p["ln1"]["scale"]), eps)
    a = p["attn"]
    q = _mm(h, f32(a["wq"]), "sd,dhk->shk", low, (0,)) + f32(a["bq"])
    k = _mm(h, f32(a["wk"]), "sd,dhk->shk", low, (0,)) + f32(a["bk"])
    v = _mm(h, f32(a["wv"]), "sd,dhk->shk", low, (0,)) + f32(a["bv"])
    pos = jnp.arange(s)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    nh, kvh, dh = q.shape[1], k.shape[1], q.shape[2]
    g = nh // kvh
    qg = q.reshape(s, kvh, g, dh)
    scores = jnp.einsum("qkgd,skd->kgqs", qg, k) / np.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(s, nh, dh)
    if low:
        o = _q8(o, (1, 2))
        wo = _q8(f32(a["wo"]), (0, 1))
    else:
        wo = f32(a["wo"])
    x = x + jnp.einsum("shk,hkd->sd", o, wo)
    h = _rms(x, f32(p["ln2"]["scale"]), eps)
    m = p["mlp"]
    gate = _mm(h, f32(m["w_gate"]), "sd,df->sf", low, (0,))
    up = _mm(h, f32(m["w_up"]), "sd,df->sf", low, (0,))
    return x + _mm(jax.nn.silu(gate) * up, f32(m["w_down"]), "sf,fd->sd",
                   low, (0,))


def _hidden(params, tokens, cfg, low):
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)

        def body(x, p):
            return _layer(x, p, cfg, low), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return _rms(x, params["final_norm"]["scale"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


def _head(params, x, cfg, low):
    w = (params["embed"]["table"].T if cfg["tie_word_embeddings"]
         else params["lm_head"]["w"]).astype(jnp.float32)
    if low:
        x, w = _q8(x, (1,)), _q8(w, (0,))
    with jax.default_matmul_precision("highest"):
        return x @ w


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "control", "width"))
def _gap_program(params, tokens, start, cfg_items, control, width):
    """Gaps at the ``width`` positions from ``start`` on: the reference's
    best logit minus its logit of the token chosen after the position,
    ``tokens[j + 1]`` or, under ``control``, the token the fp8 path puts
    first.  Only those positions go through the head."""
    cfg = dict(cfg_items)
    pick = lambda x: jax.lax.dynamic_slice_in_dim(x, start, width)  # noqa
    ref = _head(params, pick(_hidden(params, tokens, cfg, False)), cfg, False)
    if control:
        chosen = jnp.argmax(_head(params, pick(_hidden(params, tokens, cfg,
                                                       True)), cfg, True), -1)
    else:
        chosen = pick(jnp.roll(tokens, -1))
    return ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]


_KEYS = ("rms_norm_eps", "rope_theta", "tie_word_embeddings")


def gaps(params, cfg: Dict, seq: Sequence[int], start: int, max_seq: int,
         width: int, control: bool = False) -> np.ndarray:
    """Gaps of the tokens at positions ``start .. len(seq)-1`` of ``seq``
    (at most ``width`` of them).

    Without ``control``: how far each of those tokens' reference logit lies
    below the reference's best at the position before it (0 where the
    token is the reference's own greedy pick).  With ``control``: the same
    gap for the token the fp8 control puts first there instead."""
    n = len(seq) - start
    if not 0 < n <= width <= max_seq:
        raise ValueError(f"{n} served tokens, width {width}")
    items = tuple((k, cfg[k]) for k in _KEYS)
    toks = np.zeros(max_seq, np.int32)
    toks[:len(seq)] = seq
    s0 = min(start - 1, max_seq - width)      # the slice stays in bounds
    g = np.asarray(_gap_program(params, jnp.asarray(toks), s0, items,
                                control, width))
    return g[start - 1 - s0:start - 1 - s0 + n]


def widest_gap(params, cfg: Dict, served: Sequence[Tuple[Sequence[int],
                                                       Sequence[int]]],
               max_seq: int, width: int, control: bool = False) -> float:
    """The widest gap over every served token of ``served`` (pairs of
    prompt and served tokens)."""
    worst = 0.0
    for prompt, out in served:
        g = gaps(params, cfg, list(prompt) + list(out), len(prompt),
                 max_seq, width, control)
        worst = max(worst, float(g.max()))
    return worst
