"""Plain reference of a Qwen2 decoder, and the comparison that decides
``correct``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
with no kernel, cache or batching: embed, then per layer RMSNorm, GQA
attention with 1-D rotary embeddings (rotate-half form, as published for
Qwen2; Qwen2-VL's M-RoPE gives text tokens equal temporal, height and
width positions and so reduces to the same rotation) and QKV bias, then
RMSNorm and a SwiGLU MLP, each with a residual; final RMSNorm and the
head (tied to the embedding or not).  It imports nothing of the program
and reads the weights that ``weights.py`` made.

One program covers every request: the sequence (prompt plus served
tokens) is padded at its end to ``max_seq``, which causal attention keeps
from every real position.

``low=True`` is the control.  Every matrix product takes its weights
scaled per output channel and its activations per token into fp8 (e4m3,
round to nearest, the scale putting each row's largest magnitude at the
format's largest), the next precision below the served bf16, as fp8
serving computes.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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
