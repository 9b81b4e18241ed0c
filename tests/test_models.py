"""Model substrate: numeric equivalences the zoo depends on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import linear_scan as lin
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import apply_mrope, apply_rope, rms_norm, layer_norm


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# attention: the three paths agree
# ---------------------------------------------------------------------------

def _mk_attn(kvh=2, h=4, dh=16, d=32, chunk=32):
    cfg = attn.AttnConfig(d_model=d, num_heads=h, num_kv_heads=kvh,
                          head_dim=dh, chunk_size=chunk, chunk_threshold=10**9)
    p = attn.init_attn(jax.random.PRNGKey(0), cfg)
    return cfg, p


def test_chunked_attention_equals_full():
    cfg, p = _mk_attn()
    x = _rand(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    full = attn.attention(p, x, cfg)
    chunked = attn.attention(p, x, cfg._replace(chunk_threshold=0))
    np.testing.assert_allclose(chunked, full, rtol=2e-4, atol=2e-5)


def test_prefill_then_decode_equals_full_forward():
    """KV-cache decode is bit-compatible with running the whole sequence."""
    cfg, p = _mk_attn()
    b, s = 2, 17
    x = _rand(jax.random.PRNGKey(2), (b, s, cfg.d_model))
    full = attn.attention(p, x, cfg)

    cache = attn.init_kv_cache(b, 32, cfg, jnp.float32)
    y_pre, cache = attn.prefill_into_cache(p, x[:, :s - 1], cfg, cache)
    y_dec, cache = attn.decode_attention(p, x[:, s - 1:], cfg, cache)
    np.testing.assert_allclose(y_pre, full[:, :s - 1], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(y_dec, full[:, s - 1:], rtol=1e-3, atol=1e-4)
    assert cache.length.shape == (b,)          # per-row lengths
    assert (np.asarray(cache.length) == s).all()


def test_gqa_grouping_matches_repeated_kv():
    """GQA == MHA with each KV head repeated G times."""
    cfg, p = _mk_attn(kvh=2, h=4)
    x = _rand(jax.random.PRNGKey(3), (1, 24, cfg.d_model))
    out = attn.attention(p, x, cfg)

    cfg_mha = cfg._replace(num_kv_heads=4)
    p_mha = dict(p)
    p_mha["wk"] = jnp.repeat(p["wk"], 2, axis=1)
    p_mha["wv"] = jnp.repeat(p["wv"], 2, axis=1)
    out_mha = attn.attention(p_mha, x, cfg_mha)
    np.testing.assert_allclose(out, out_mha, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def test_rope_preserves_norm_and_relative_property():
    x = _rand(jax.random.PRNGKey(4), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    y = apply_rope(x, pos)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_mrope_reduces_to_rope_for_text_tokens():
    """Qwen2-VL property: tokens with t==h==w get plain 1D RoPE."""
    s, dh = 12, 24
    x = _rand(jax.random.PRNGKey(5), (1, s, 2, dh))
    pos = jnp.arange(s)[None]
    pos3 = jnp.broadcast_to(pos, (3, 1, s))
    sections = (4, 4, 4)        # sums to dh//2
    got = apply_mrope(x, pos3, sections)
    want = apply_rope(x, pos)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def test_rms_norm_unit_scale():
    x = _rand(jax.random.PRNGKey(6), (4, 32)) * 10
    y = rms_norm(x, {"scale": jnp.ones((32,))})
    rms = jnp.sqrt(jnp.mean(y.astype(jnp.float32) ** 2, -1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_layer_norm_zero_mean_unit_var():
    x = _rand(jax.random.PRNGKey(7), (4, 32)) * 3 + 5
    y = layer_norm(x, {"scale": jnp.ones((32,)), "bias": jnp.zeros((32,))})
    np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-4)
    np.testing.assert_allclose(jnp.std(y, -1), 1.0, rtol=1e-2)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _moe_cfg(e=8, k=2, shared=1):
    return moe_mod.MoEConfig(d_model=32, d_ff_expert=16, num_experts=e,
                             top_k=k, num_shared_experts=shared,
                             d_ff_shared=16 * shared)


def test_moe_output_shape_and_aux_loss():
    cfg = _moe_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = _rand(jax.random.PRNGKey(1), (2, 8, 32))
    y, aux, _ = moe_mod.moe_mlp(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all()
    assert float(aux) >= 0.0


def test_moe_uniform_router_balanced_aux():
    """A uniform router must not be penalized more than a skewed one."""
    cfg = _moe_cfg(e=4, k=1, shared=0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = _rand(jax.random.PRNGKey(2), (1, 64, 32))
    p_uni = dict(p, router=jnp.zeros_like(p["router"]))
    _, aux_uni, _ = moe_mod.moe_mlp(p_uni, x, cfg)
    # skew: every token to expert 0
    skew = jnp.zeros_like(p["router"]).at[:, 0].set(0.0)
    p_skew = dict(p, router=skew + jnp.array([10.0, 0, 0, 0]))
    _, aux_skew, _ = moe_mod.moe_mlp(p_skew, x, cfg)
    assert float(aux_uni) <= float(aux_skew) + 1e-6


def test_moe_top1_selects_argmax_expert():
    # dispatch is dropless: every token reaches its expert, however many
    # tokens pick the same one
    cfg = _moe_cfg(e=4, k=1, shared=0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = _rand(jax.random.PRNGKey(3), (1, 4, 32))
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    y, _, _ = moe_mod.moe_mlp(p, x, cfg)
    # manual top-1 dispatch oracle (top-1 routing weight softmaxes to 1)
    e_idx = jnp.argmax(logits, -1)
    outs = []
    for t in range(4):
        e = int(e_idx[0, t])
        h = x[0, t] @ p["w_gate"][e]
        u = x[0, t] @ p["w_up"][e]
        outs.append((jax.nn.silu(h) * u) @ p["w_down"][e])
    np.testing.assert_allclose(y[0], jnp.stack(outs), rtol=2e-3, atol=2e-4)


def test_moe_capacity_dispatch_lossless_matches_dropless():
    # capacity_factor = E/topk leaves room for every pair: the training
    # dispatch then gives the serving one's output and aux loss
    cfg = _moe_cfg(e=8, k=2, shared=1)._replace(capacity_factor=4.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = _rand(jax.random.PRNGKey(4), (2, 8, 32))
    y, aux, _ = moe_mod.moe_mlp(p, x, cfg)
    y_cap, aux_cap = moe_mod.moe_mlp_capacity(p, x, cfg)
    np.testing.assert_allclose(y_cap, y, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(aux_cap, aux, rtol=1e-6)


def test_moe_capacity_dispatch_drops_past_capacity():
    # every token to expert 0 at capacity_factor 1: capacity is T*k/E
    # pairs, so only the first 4 of 16 tokens get an expert output
    cfg = _moe_cfg(e=4, k=1, shared=0)._replace(capacity_factor=1.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, 0].set(1.0))
    x = jnp.abs(_rand(jax.random.PRNGKey(5), (1, 16, 32)))
    y_cap, _ = moe_mod.moe_mlp_capacity(p, x, cfg)
    y, _, _ = moe_mod.moe_mlp(p, x, cfg)
    np.testing.assert_allclose(y_cap[0, :4], y[0, :4], rtol=2e-3, atol=2e-4)
    assert float(jnp.max(jnp.abs(y_cap[0, 4:]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(y[0, 4:]), -1))) > 0.0
    with pytest.raises(ValueError, match="every expert"):
        moe_mod.moe_mlp_capacity(p, x, cfg._replace(held_experts=2))


def test_moe_active_params_counting():
    cfg = _moe_cfg(e=8, k=2, shared=1)
    active = moe_mod.count_active_params(cfg)
    total_routed = 3 * 32 * 16 * 8
    active_routed = 3 * 32 * 16 * 2
    assert active < total_routed
    assert active >= active_routed


# ---------------------------------------------------------------------------
# linear scan (SSD / gated linear attention): chunked == sequential
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_chunked_linear_attention_equals_sequential(chunk):
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    b, s, h, d = 2, 128, 2, 8
    q = _rand(ks[0], (b, s, h, d)); k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    lf = -jax.nn.softplus(_rand(ks[3], (b, s, h)))
    li = -jax.nn.softplus(_rand(ks[4], (b, s, h)))
    y_c, (C_c, n_c) = lin.chunked_linear_attention(q, k, v, lf, li,
                                                   chunk_size=chunk)
    y_s, (C_s, n_s) = lin.sequential_linear_attention(q, k, v, lf, li)
    np.testing.assert_allclose(y_c, y_s, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(C_c, C_s, rtol=2e-3, atol=2e-3)


def test_linear_attention_state_carries_across_segments():
    """Processing [a;b] at once == processing a, then b with carried state."""
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    b, s, h, d = 1, 64, 2, 8
    q = _rand(ks[0], (b, s, h, d)); k = _rand(ks[1], (b, s, h, d))
    v = _rand(ks[2], (b, s, h, d))
    lf = -jax.nn.softplus(_rand(ks[3], (b, s, h)))
    li = -jax.nn.softplus(_rand(ks[4], (b, s, h)))
    y_all, _ = lin.sequential_linear_attention(q, k, v, lf, li)
    half = s // 2
    y1, st = lin.sequential_linear_attention(
        q[:, :half], k[:, :half], v[:, :half], lf[:, :half], li[:, :half])
    y2, _ = lin.sequential_linear_attention(
        q[:, half:], k[:, half:], v[:, half:], lf[:, half:], li[:, half:],
        initial_state=st)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_all,
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Mamba2 block: prefill/decode state equivalence
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mamba2_decode_matches_block_forward():
    cfg = ssm_mod.Mamba2Config(d_model=32, d_state=8, head_dim=8,
                               chunk_size=16)
    p = ssm_mod.init_mamba2_block(jax.random.PRNGKey(0), cfg)
    b, s = 1, 24
    x = _rand(jax.random.PRNGKey(1), (b, s, 32))
    y_full = ssm_mod.apply_mamba2_block(p, x, cfg)

    st = ssm_mod.init_mamba2_state(b, cfg)
    y_pre, st = ssm_mod.apply_mamba2_block(p, x[:, :s - 1], cfg,
                                           initial_state=st,
                                           return_state=True)
    y_dec, _ = ssm_mod.mamba2_decode(p, x[:, s - 1:], cfg, st)
    np.testing.assert_allclose(y_dec, y_full[:, s - 1:], rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# xLSTM blocks: decode == prefill last step
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mlstm_decode_matches_forward():
    cfg = xlstm_mod.XLSTMConfig(d_model=32, num_heads=2, chunk_size=16)
    p = xlstm_mod.init_mlstm_block(jax.random.PRNGKey(0), cfg)
    b, s = 1, 17
    x = _rand(jax.random.PRNGKey(1), (b, s, 32))
    y_full = xlstm_mod.apply_mlstm_block(p, x, cfg)

    st = xlstm_mod.init_mlstm_state(b, cfg)
    y_pre, st = xlstm_mod.apply_mlstm_block(p, x[:, :s - 1], cfg,
                                            initial_state=st,
                                            return_state=True)
    y_dec, _ = xlstm_mod.mlstm_decode(p, x[:, s - 1:], cfg, st)
    np.testing.assert_allclose(y_dec, y_full[:, s - 1:], rtol=2e-2, atol=2e-2)


def test_slstm_decode_matches_forward():
    cfg = xlstm_mod.XLSTMConfig(d_model=32, num_heads=2, chunk_size=16)
    p = xlstm_mod.init_slstm_block(jax.random.PRNGKey(0), cfg)
    b, s = 1, 9
    x = _rand(jax.random.PRNGKey(2), (b, s, 32))
    y_full = xlstm_mod.apply_slstm_block(p, x, cfg)
    st = xlstm_mod.init_slstm_state(b, cfg)
    y_pre, st = xlstm_mod.apply_slstm_block(p, x[:, :s - 1], cfg,
                                            initial_state=st,
                                            return_state=True)
    y_dec, _ = xlstm_mod.slstm_decode(p, x[:, s - 1:], cfg, st)
    np.testing.assert_allclose(y_dec, y_full[:, s - 1:], rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# attention softmax modes (§Perf hillclimb 1): all paths agree incl. grads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fused", "kernel"])
def test_attention_modes_match_naive(mode):
    cfg, p = _mk_attn()
    x = _rand(jax.random.PRNGKey(21), (2, 96, cfg.d_model))
    naive = attn.attention(p, x, cfg)
    got = attn.attention(p, x, cfg._replace(softmax_mode=mode))
    np.testing.assert_allclose(got, naive, rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("mode", ["fused", "kernel"])
def test_attention_modes_grads_match(mode):
    cfg, p = _mk_attn()
    x = _rand(jax.random.PRNGKey(22), (1, 64, cfg.d_model))

    def loss(params, xx, m):
        return (attn.attention(params, xx, cfg._replace(softmax_mode=m))
                ** 2).sum()

    gx = jax.grad(loss, argnums=1)(p, x, "naive")
    gx2 = jax.grad(loss, argnums=1)(p, x, mode)
    np.testing.assert_allclose(gx2, gx, rtol=2e-3, atol=2e-4)
    gp = jax.grad(loss)(p, x, "naive")
    gp2 = jax.grad(loss)(p, x, mode)
    for k in gp:
        np.testing.assert_allclose(gp2[k], gp[k], rtol=2e-3, atol=2e-4)


def test_kernel_mode_chunked_path():
    cfg, p = _mk_attn()
    cfg = cfg._replace(chunk_threshold=48, chunk_size=32,
                       softmax_mode="kernel")
    x = _rand(jax.random.PRNGKey(23), (1, 96, cfg.d_model))
    want = attn.attention(p, x, cfg._replace(softmax_mode="naive"))
    got = attn.attention(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


def test_decode_attention_token_matches_decode_attention():
    cfg, p = _mk_attn()
    b, s = 2, 12
    x = _rand(jax.random.PRNGKey(24), (b, s, cfg.d_model))
    full = attn.attention(p, x, cfg)
    cache = attn.init_kv_cache(b, 16, cfg, jnp.float32)
    _, cache = attn.prefill_into_cache(p, x[:, :s - 1], cfg, cache)
    y, k_t, v_t = attn.decode_attention_token(
        p, x[:, s - 1:], cfg, cache.k, cache.v, cache.length)
    np.testing.assert_allclose(y, full[:, s - 1:], rtol=1e-3, atol=1e-4)
    assert k_t.shape == (b, 1, cfg.num_kv_heads, cfg.head_dim)


@pytest.mark.slow
def test_inplace_decode_stack_feature():
    """features.decode_inplace_cache path == default path (tiny LM)."""
    from repro.core.features import default_features
    from repro.models.lm import LM, LMConfig
    cfg = LMConfig(name="t", family="dense", vocab=64, d_model=32,
                   n_layers=2, num_heads=4, num_kv_heads=2, d_ff=64)
    f0 = default_features().with_(remat_policy="none")
    f1 = f0.with_(decode_inplace_cache=True)
    lm0, lm1 = LM(cfg, f0), LM(cfg, f1)
    p = lm0.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(10)[None].astype(jnp.int32) % 64}
    st0 = lm0.init_decode_state(1, 16)
    st1 = lm1.init_decode_state(1, 16)
    l0, st0 = lm0.prefill(p, batch, st0)
    l1, st1 = lm1.prefill(p, batch, st1)
    tok = jnp.argmax(l0, -1)[:, None].astype(jnp.int32)
    d0, _ = lm0.decode_step(p, tok, st0)
    d1, _ = lm1.decode_step(p, tok, st1)
    # bf16 compute: the two-part softmax reassociates the reduction
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d0, np.float32),
                               rtol=2e-2, atol=2e-2)
