"""Seeded weights for a Qwen2-style decoder, made on the device.

The tree has the layout the serving program reads (``embed.table``,
``final_norm.scale``, ``blocks`` stacked over layers, ``lm_head.w`` when
the head is untied), in the dtype it is served in, built by one jitted
call from the seed.  Every matrix and bias is N(0, std) with std the
configuration's ``initializer_range`` (0.02 in Qwen2's published
configs); norm scales are 1.  Biases are drawn too, so that a fault on
the bias path moves the logits.

With std 0.02 the residual stream is carried by the layers and not by the
input embedding, so greedy decoding of these weights does not fall into
repeating its input token: the tokens depend on the attention over the
context, which is what the output comparison has to see.

The plain reference (``reference.py``) reads this same tree; it is made
here, by the benchmark, and not by the program.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def shapes(cfg: Dict) -> Dict:
    """Leaf shapes of one layer and of the rest, from the config file's
    published keys."""
    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
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


def make(cfg: Dict, seed: int, dtype=jnp.bfloat16):
    """The whole tree, on the default device, from ``seed``.  Layers are
    drawn one at a time inside the program (``lax.map``), so the draw
    needs the memory of one layer's random bits and no more."""
    sh = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    n = cfg["num_hidden_layers"]

    @jax.jit
    def build(key):
        k_rest, k_layers = jax.random.split(key)
        rest = _fill(k_rest, sh["rest"], std, dtype)
        blocks = jax.lax.map(lambda k: _fill(k, sh["layer"], std, dtype),
                             jax.random.split(k_layers, n))
        return dict(rest, blocks=blocks)

    # seeds run past 32 bits: fold the high word in
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.block_until_ready(build(key))

