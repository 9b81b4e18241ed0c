"""The system under test, built from a configuration file: the program's
``LM`` and ``Engine`` over the paged ``KVPool``, with the registry's
default kernels and the benchmark's own weights."""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.features import default_features
from repro.models.lm import LM, LMConfig
from repro.serve import Engine, ServeConfig


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one token over every layer, bf16."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * dh * 2


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
    dh = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
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


def engine(cfg: Dict, params, scfg: Optional[ServeConfig] = None) -> Engine:
    lm = LM(lm_config(cfg), default_features().with_(remat_policy="none"),
            dtype=jnp.bfloat16)
    return Engine(lm, params, scfg or serve_config(cfg))
