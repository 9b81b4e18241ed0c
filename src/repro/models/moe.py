"""Mixture-of-Experts MLP: top-k router, dropless dispatch over the held
experts (serving), capacity dispatch (training), shared experts.

Covers qwen2-moe-a2.7b (60 routed top-4 + 4 shared-expert "always on" FFNs)
and qwen3-moe-235b (128 routed top-8, no shared experts).

The router keeps its full width: softmax over every routed expert in f32,
top-k, the k weights renormalised to sum to 1.  A layer may hold only some
of the experts, ``first_expert .. first_expert + held - 1`` (one chip's
share under expert parallelism; all of them by default): its weights hold
those experts alone, and it computes their part of the result.  Pairs
(token, k) routed to an expert it does not hold add nothing here; the
chips that hold them add theirs.

Dispatch is **dropless**: the (token, k) pairs that fall on held experts
are sorted by expert and run through one grouped matmul per projection
(``kernels/grouped_matmul.py``, through the registry), exactly those rows
and no capacity padding; the outputs come back to their pairs by the
inverse permutation and are summed per token with the gate weights, in
the order of the token's k choices.  A token's output therefore depends
on nothing else in its batch.

Each call also returns its counts, int32 ``[tokens, pairs routed, pairs
held, held experts with a row]`` (:data:`COUNTS`), which the serving
programs add up on the device.

Training keeps the **capacity** dispatch, :func:`moe_mlp_capacity`
(GShard-style scatter into ``[E, C, D]`` buffers, tokens past capacity
dropped): with experts sharded over the ``model`` axis, XLA SPMD turns
its scatter/gather into all-to-all.  The dropless dispatch's global sort
over data-sharded tokens does not partition: compiled for a described
v5e 16x16, qwen3-moe-235b-a22b's train_4k step at two layers peaks at
15.0 GB a chip against the capacity form's 3.0 GB.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import Params, Specs, truncated_normal_init

__all__ = ["MoEConfig", "init_moe", "moe_specs", "moe_mlp",
           "moe_mlp_capacity", "COUNTS"]

#: the counts :func:`moe_mlp` returns, in order
COUNTS = ("moe_tokens", "moe_rows_routed", "moe_rows_held",
          "moe_expert_loads")


class MoEConfig(NamedTuple):
    d_model: int
    d_ff_expert: int            # per-expert FFN width
    num_experts: int            # routed experts: the router's width
    top_k: int
    num_shared_experts: int = 0 # always-on experts (qwen2-moe: 4)
    d_ff_shared: int = 0        # width of the fused shared-expert FFN
    capacity_factor: float = 1.25   # training's capacity dispatch only
    router_aux_weight: float = 0.001
    first_expert: int = 0       # the experts held here: first_expert ..
    held_experts: int = 0       # .. + held - 1 (0: every expert)

    @property
    def held(self) -> int:
        return self.held_experts or self.num_experts


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    kr, k1, k2, k3, s1, s2, s3 = jax.random.split(key, 7)
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.held
    std = 1.0 / np.sqrt(d)
    p = {
        "router": truncated_normal_init(kr, (d, cfg.num_experts),
                                        jnp.float32, std),
        "w_gate": truncated_normal_init(k1, (e, d, f), dtype, std),
        "w_up": truncated_normal_init(k2, (e, d, f), dtype, std),
        "w_down": truncated_normal_init(k3, (e, f, d), dtype, 1.0 / np.sqrt(f)),
    }
    if cfg.num_shared_experts:
        fs = cfg.d_ff_shared or cfg.d_ff_expert * cfg.num_shared_experts
        p["shared_gate"] = truncated_normal_init(s1, (d, fs), dtype, std)
        p["shared_up"] = truncated_normal_init(s2, (d, fs), dtype, std)
        p["shared_down"] = truncated_normal_init(s3, (fs, d), dtype,
                                                 1.0 / np.sqrt(fs))
        p["shared_coef"] = truncated_normal_init(key, (d, 1), jnp.float32, std)
    return p


def moe_specs(cfg: MoEConfig) -> Specs:
    s = {
        "router": ("embed", "experts"),
        "w_gate": ("experts", "embed", "expert_ff"),
        "w_up": ("experts", "embed", "expert_ff"),
        "w_down": ("experts", "expert_ff", "embed"),
    }
    if cfg.num_shared_experts:
        s["shared_gate"] = ("embed", "ff")
        s["shared_up"] = ("embed", "ff")
        s["shared_down"] = ("ff", "embed")
        s["shared_coef"] = ("embed", None)
    return s


def _route(p: Params, xt: jnp.ndarray, cfg: MoEConfig):
    """Router in f32 over every routed expert: (gate [T,K] renormalised,
    expert ids [T,K], Switch-style load-balancing aux loss)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [T,E]
    gate, idx = jax.lax.top_k(probs, cfg.top_k)                   # [T,K]
    gate = gate / jnp.sum(gate, -1, keepdims=True)                # renorm
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], cfg.num_experts), axis=0)
    aux = cfg.router_aux_weight * cfg.num_experts * jnp.sum(me * ce)
    return gate, idx, aux


def _add_shared(p: Params, xt: jnp.ndarray, out: jnp.ndarray,
                cfg: MoEConfig) -> jnp.ndarray:
    """``out`` plus the shared experts (dense SwiGLU, gated residual:
    qwen2-moe); ``out`` itself when there are none."""
    if not cfg.num_shared_experts:
        return out
    sg = jnp.einsum("td,df->tf", xt, p["shared_gate"].astype(xt.dtype))
    su = jnp.einsum("td,df->tf", xt, p["shared_up"].astype(xt.dtype))
    sh = jnp.einsum("tf,fd->td", jax.nn.silu(sg) * su,
                    p["shared_down"].astype(xt.dtype))
    coef = jax.nn.sigmoid(
        jnp.einsum("td,dz->tz", xt.astype(jnp.float32), p["shared_coef"]))
    return out + sh * coef.astype(xt.dtype)


def moe_mlp(p: Params, x: jnp.ndarray, cfg: MoEConfig, *,
            valid: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [B,S,D] -> (out [B,S,D], aux_loss scalar f32, counts int32[4]).

    ``valid`` [B,S] (optional) marks the real tokens: the others (padding,
    decode rows with no request) route to no expert and count for
    nothing.
    """
    from repro.kernels import registry
    b, s, d = x.shape
    t, k, held = b * s, cfg.top_k, cfg.held
    xt = x.reshape(t, d)

    with jax.named_scope("moe_router"):
        gate, idx, aux = _route(p, xt, cfg)
        local = idx - cfg.first_expert
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid.reshape(t, 1)
        # pairs on held experts first, by expert; the rest after them
        key = jnp.where(mine, local, held).reshape(t * k)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        rows = xt[order // k]                                     # [T*K,D]

    with jax.named_scope("moe_experts"):
        impl = registry.select("grouped_matmul")

        def gmm(a, w):
            return registry.run("grouped_matmul", a, w.astype(x.dtype),
                                sizes, impl=impl)

        h = jax.nn.silu(gmm(rows, p["w_gate"])) * gmm(rows, p["w_up"])
        y = gmm(h.astype(x.dtype), p["w_down"])                   # f32

    with jax.named_scope("moe_combine"):
        held_rows = jnp.sum(sizes)
        y = jnp.where((jnp.arange(t * k) < held_rows)[:, None], y, 0.0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        y = y[back].reshape(t, k, d)
        w = jnp.where(mine, gate, 0.0)
        out = jnp.sum(y * w[..., None], axis=1).astype(x.dtype)

    out = _add_shared(p, xt, out, cfg)
    tokens = (jnp.sum(valid, dtype=jnp.int32) if valid is not None
              else jnp.int32(t))
    counts = jnp.stack([tokens, tokens * k, held_rows,
                        jnp.sum(sizes > 0, dtype=jnp.int32)])
    return out.reshape(b, s, d), aux.astype(jnp.float32), counts


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    cap = int(np.ceil(tokens * cfg.top_k * cfg.capacity_factor
                      / cfg.num_experts))
    return max(cap, cfg.top_k)


def moe_mlp_capacity(p: Params, x: jnp.ndarray, cfg: MoEConfig,
                     constrain_fn=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The training dispatch: x [B,S,D] -> (out [B,S,D], aux_loss f32).

    Tokens are scattered into ``[E, C, D]`` capacity buffers, positions
    from a cumsum over the [T*K, E] assignment matrix; pairs past an
    expert's capacity are dropped (their gate weight zeroed).  The layer
    must hold every expert.  ``constrain_fn(arr, logical_axes)``
    (optional) pins the dispatch tensors' shardings: token-major arrays
    over the data axes, the buffers over (experts -> model, capacity ->
    data).
    """
    if cfg.held != cfg.num_experts:
        raise ValueError("the capacity dispatch needs every expert held")
    b, s, d = x.shape
    t, k, e = b * s, cfg.top_k, cfg.num_experts
    cap = _capacity(t, cfg)
    xt = x.reshape(t, d)
    cst = constrain_fn or (lambda a, axes: a)
    gate, idx, aux = _route(p, xt, cfg)

    # positions within each expert's buffer (a flat cumsum: a blocked one
    # lowers to a triangular matmul and breaks SPMD sharding propagation)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)              # [T,K,E]
    flat = cst(onehot.reshape(t * k, e), ("moe_tokens", None))
    pos = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, axis=-1)
    eid = idx.reshape(t * k)
    keep = pos < cap
    # gate weights combine in the compute dtype: an f32 gate here upcasts
    # every [T*K, D] dispatch array to f32
    w = (gate.reshape(t * k) * keep).astype(x.dtype)

    src = cst(jnp.repeat(xt, k, axis=0), ("moe_tokens", "embed"))
    pos_c = jnp.where(keep, pos, cap - 1)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[eid, pos_c].add(src * keep[:, None].astype(x.dtype))
    buf = cst(buf, ("experts", "moe_capacity", "embed"))

    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                         p["w_down"].astype(x.dtype))
    out_buf = cst(out_buf, ("experts", "moe_capacity", "embed"))

    gathered = cst(out_buf[eid, pos_c], ("moe_tokens", "embed"))  # [T*K,D]
    out = jnp.sum((gathered * w[:, None]).reshape(t, k, d), axis=1)
    out = _add_shared(p, xt, out, cfg)
    return out.reshape(b, s, d), aux.astype(jnp.float32)


def count_active_params(cfg: MoEConfig) -> int:
    """Per-token active params in this MoE layer (for 6*N_active*D)."""
    per_expert = 3 * cfg.d_model * cfg.d_ff_expert
    active = cfg.top_k * per_expert + cfg.d_model * cfg.num_experts
    if cfg.num_shared_experts:
        fs = cfg.d_ff_shared or cfg.d_ff_expert * cfg.num_shared_experts
        active += 3 * cfg.d_model * fs + cfg.d_model
    return active
