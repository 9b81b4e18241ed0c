"""Qwen3-MoE's block served through the paged engine, against a plain
float32 reference kept here: QK-norm, a softmax router over every routed
expert with its top-k renormalised, and the dropless MoE over the experts
a model holds (one chip's share), with what the absent experts would add
left out.

Tolerances.  The program serves in float32 here, so it differs from the
reference only by float32 rounding taken in another order (grouped
matmuls, paged attention, a one-token decode against cached K/V): about
1.2e-6 on logits of magnitude 3.  ``ATOL`` lies some 170 times above
that and over 100 times below what computing every product in bfloat16
moves them (0.025-0.44 on the same requests), which the reference's own
bfloat16 twin must show.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.features import default_features
from repro.models import moe as moe_mod
from repro.models.lm import LM
from repro.serve import BatchScheduler, Engine, Request, ServeConfig

#: the smoke config at two layers, holding experts 4..11 of 16
CFG = dataclasses.replace(get_arch("qwen3-moe-235b-a22b").smoke, n_layers=2,
                          moe_first_expert=4, moe_held_experts=8)
ATOL = 2e-4


def _params(cfg, seed=0):
    """The program's initial weights with every norm scale (QK-norm's
    included) drawn around 1, so that a scale left out shows."""
    p = LM(cfg).init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def scale(path, a):
        if path[-1].key == "scale":
            return 1.0 + 0.3 * jax.random.normal(next(keys), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(scale, p)


# ------------------------------------------------------------ reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, dh = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def ref_moe(m, h, mc, dt=jnp.float32):
    """The MoE half over tokens ``h`` [S, D]: each held expert's SwiGLU on
    every token, weighted by its renormalised top-k gate where the token
    routed to it; plus the shared experts' gated SwiGLU."""
    c = lambda a: jnp.asarray(a, dt)  # noqa: E731
    probs = jax.nn.softmax((c(h) @ c(m["router"])).astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, mc.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros(h.shape, dt)
    for j in range(m["w_gate"].shape[0]):
        g = jnp.sum(jnp.where(idx == mc.first_expert + j, top, 0.0), -1)
        ffn = (jax.nn.silu(c(h) @ c(m["w_gate"][j])) * (c(h) @ c(m["w_up"][j]))
               ) @ c(m["w_down"][j])
        out = out + c(g)[:, None] * ffn
    if mc.num_shared_experts:
        out = out + ref_shared(m, h, dt)
    return out


def ref_shared(m, h, dt=jnp.float32):
    c = lambda a: jnp.asarray(a, dt)  # noqa: E731
    sh = (jax.nn.silu(c(h) @ c(m["shared_gate"])) * (c(h) @ c(m["shared_up"]))
          ) @ c(m["shared_down"])
    return sh * jax.nn.sigmoid(c(h) @ c(m["shared_coef"]))


def reference(params, tokens, cfg, dt=jnp.float32):
    """Logits [S, V] of ``tokens`` by the full forward pass, every product
    in ``dt`` (float32 at ``highest``, or the bfloat16 twin)."""
    c = lambda a: jnp.asarray(a, dt)  # noqa: E731
    eps, mc = cfg.norm_eps, cfg.moe_config()
    with jax.default_matmul_precision("highest"):
        x = c(params["embed"]["table"])[jnp.asarray(tokens)]
        s = x.shape[0]
        for layer in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[layer], params["blocks"])
            a = p["attn"]
            h = _rms(x, c(p["ln1"]["scale"]), eps)
            q = jnp.einsum("sd,dhk->shk", h, c(a["wq"]))
            k = jnp.einsum("sd,dhk->shk", h, c(a["wk"]))
            v = jnp.einsum("sd,dhk->shk", h, c(a["wv"]))
            q = _rope(_rms(q, c(a["q_norm"]["scale"]), eps), cfg.rope_theta)
            k = _rope(_rms(k, c(a["k_norm"]["scale"]), eps), cfg.rope_theta)
            kvh, dh = k.shape[1], k.shape[2]
            qg = q.reshape(s, kvh, -1, dh)
            sc = jnp.einsum("qkgd,skd->kgqs", qg, k) / np.sqrt(dh)
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            pr = jax.nn.softmax(jnp.where(causal, sc.astype(jnp.float32),
                                          -jnp.inf), -1)
            o = jnp.einsum("kgqs,skd->qkgd", c(pr), v).reshape(s, -1, dh)
            x = x + jnp.einsum("shk,hkd->sd", o, c(a["wo"]))
            x = x + ref_moe(p["moe"], _rms(x, c(p["ln2"]["scale"]), eps), mc,
                            dt)
        x = _rms(x, c(params["final_norm"]["scale"]), eps)
        w = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["lm_head"]["w"])
        return (x @ c(w)).astype(jnp.float32)


# ------------------------------------------------------------- serving
def _engine(params, slots=4):
    lm = LM(CFG, default_features().with_(remat_policy="none"),
            dtype=jnp.float32)
    return Engine(lm, params, ServeConfig(max_seq=64, batch_slots=slots,
                                          page_size=4, admission_chunk=1))


def _serve(eng, requests):
    """Serve ``requests`` (rid -> (prompt, budget)) through a
    BatchScheduler, segments of one step.  Returns (scheduler, rid ->
    (served tokens, the logits each was chosen from [n, V]))."""
    seen, admitted = [], {}
    segment, prefill = eng.decode_segment, eng.prefill_slot

    def recording(steps):
        fn = segment(steps)

        def call(params, state, logits, rng):
            seen.append(np.asarray(logits))      # before it is donated
            return fn(params, state, logits, rng)
        return call

    def admit(state, logits, prompt, slot, **kw):
        admitted[tuple(prompt)] = (len(seen), slot)
        return prefill(state, logits, prompt, slot, **kw)

    eng.decode_segment, eng.prefill_slot = recording, admit
    sched = BatchScheduler(eng)
    for rid, (prompt, budget) in requests.items():
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=budget))
    done = sched.run()
    out = {}
    for rid, (prompt, budget) in requests.items():
        at, slot = admitted[tuple(prompt)]
        out[rid] = (done[rid].generated,
                    np.stack([seen[at + j][slot] for j in range(budget)]))
    return sched, out


REQUESTS = {0: ([5, 17, 3, 99, 42, 7, 11], 9), 1: ([1, 2, 3], 6),
            2: (list(range(30, 52)), 5), 3: ([200, 201], 8)}


@pytest.fixture(scope="module")
def served():
    params = _params(CFG)
    return params, _serve(_engine(params), REQUESTS)


def test_prefill_and_paged_decode_match_the_reference(served):
    """Every served token's logits (the slot prefill's, then each paged
    decode step's) against the reference's full forward pass over the
    prompt and the served tokens; greedy picks its best."""
    params, (_sched, out) = served
    for rid, (prompt, budget) in REQUESTS.items():
        gen, got = out[rid]
        assert len(gen) == budget
        want = reference(params, prompt + gen, CFG)[len(prompt) - 1:-1]
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        assert [int(t) for t in np.argmax(got, -1)] == gen


def test_a_bfloat16_reference_fails_the_tolerance(served):
    params, (_sched, out) = served
    prompt, _ = REQUESTS[2]
    seq = prompt + out[2][0]
    gap = np.abs(reference(params, seq, CFG, jnp.bfloat16)
                 - reference(params, seq, CFG))
    assert float(gap.max()) > 10 * ATOL


def test_a_request_is_the_same_alone_and_in_a_full_batch(served):
    params, (_sched, out) = served
    _, alone = _serve(_engine(params), {0: REQUESTS[0]})
    assert alone[0][0] == out[0][0]
    np.testing.assert_array_equal(alone[0][1], out[0][1])


def test_scheduler_counts_routed_and_held_rows(served):
    """The MoE counters, added up on the device and fetched with each
    segment's tokens: every token through every layer routes top-k pairs
    (a row with no request none), only pairs on held experts are held,
    and an expert load is one held expert touched in a layer and a
    call."""
    _params_, (sched, _out) = served
    m, k, layers = sched.metrics, CFG.moe_top_k, CFG.n_layers
    prompts = sum(len(p) for p, _ in REQUESTS.values())
    assert m["moe_tokens.prefill"] == layers * prompts
    # each segment (one step) decodes every live row's sampled token
    assert m["moe_tokens.decode"] == layers * sum(
        b for _, b in REQUESTS.values())
    for kind in ("decode", "prefill"):
        assert m[f"moe_rows_routed.{kind}"] == k * m[f"moe_tokens.{kind}"]
        assert 0 < m[f"moe_rows_held.{kind}"] < m[f"moe_rows_routed.{kind}"]
        assert 0 < m[f"moe_expert_loads.{kind}"] <= m[f"moe_rows_held.{kind}"]
    assert m["moe_expert_loads.prefill"] <= \
        layers * len(REQUESTS) * CFG.moe_held_experts


# --------------------------------------------------------- expert shares
@pytest.mark.parametrize("shared", [0, 1], ids=["routed", "with_shared"])
def test_expert_shares_sum_to_the_uncut_layer(shared):
    """Four shares of 16 experts, each layer holding 4: their outputs
    summed, with the shared expert that every share computes counted
    once, are the uncut reference layer's; and so is the program's uncut
    layer.  Each pair is held by exactly one share."""
    mc = moe_mod.MoEConfig(d_model=32, d_ff_expert=16, num_experts=16,
                           top_k=4, num_shared_experts=shared,
                           d_ff_shared=16 * shared)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), mc)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    want = ref_moe(p, x.reshape(16, 32), mc).reshape(x.shape)
    whole, _, whole_counts = moe_mod.moe_mlp(p, x, mc)
    total, held = jnp.zeros_like(x), 0
    for first in (0, 4, 8, 12):
        part = dict(p, **{w: p[w][first:first + 4]
                          for w in ("w_gate", "w_up", "w_down")})
        out, _, counts = moe_mod.moe_mlp(
            part, x, mc._replace(first_expert=first, held_experts=4))
        total, held = total + out, held + int(counts[2])
    if shared:
        total = total - 3 * ref_shared(p, x.reshape(16, 32)).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    assert held == int(whole_counts[2]) == int(whole_counts[1]) == 16 * 4
