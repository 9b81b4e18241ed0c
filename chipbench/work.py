"""Operations and bytes the algorithm needs, from the configuration's
shapes and its family's counts, and the chips' peaks.

Model FLOPs count every multiply-add as two operations: the layers'
matrix products (the family's ``layer_matmul_params``), attention over
exactly the causal context, and the head where the program computes it
(one position per prefill, one per decoded token).  Kernel bytes are the
least a kernel must move through HBM: what it reads once and writes
once, in bf16, K and V at the family's ``kv_bytes_per_token``.

Every count is of the whole model.  On a mesh each chip does its share:
a reader compares the count over the chips with one chip's peak.
"""

from __future__ import annotations

from typing import Dict, Sequence

#: peaks of one chip by ``device_kind``, from Google Cloud's "TPU v5e"
#: documentation: dense bf16 FLOP/s, HBM bytes/s, HBM bytes
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}

BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kvh": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def attention_flops(cfg: Dict, contexts: Sequence[int]) -> float:
    """QK^T and PV for one query per entry of ``contexts`` (the keys it
    sees, itself included), over every layer and head."""
    m = dims(cfg)
    return 4.0 * m["layers"] * m["h"] * m["dh"] * float(sum(contexts))


def prefill_flops(fam, cfg: Dict, tokens: int, prefix: int) -> float:
    """One slot prefill of ``tokens`` new tokens after ``prefix`` resident
    ones: the layers for every new token, causal attention, and the head
    for the last position."""
    m = dims(cfg)
    ctx = prefix * tokens + tokens * (tokens + 1) / 2
    return (2.0 * m["layers"] * fam.layer_matmul_params(cfg) * tokens
            + 4.0 * m["layers"] * m["h"] * m["dh"] * ctx
            + 2.0 * m["d"] * m["v"])


def decode_flops(fam, cfg: Dict, contexts: Sequence[int]) -> float:
    """One decode step of rows whose new token sees ``contexts`` keys
    each: layers, attention and head per row."""
    m = dims(cfg)
    per_row = 2.0 * (m["layers"] * fam.layer_matmul_params(cfg)
                     + m["d"] * m["v"])
    return per_row * len(contexts) + attention_flops(cfg, contexts)


def paged_decode_work(fam, cfg: Dict, contexts: Sequence[int]):
    """(FLOPs, bytes) of the paged decode kernel over every layer, for one
    step of rows with ``contexts`` resident keys each: it reads each
    row's K and V once, its queries and the new token's K and V, and
    writes its output."""
    m = dims(cfg)
    n, ctx = len(contexts), float(sum(contexts))
    kv = float(fam.kv_bytes_per_token(cfg)) * (ctx + n)
    qo = 2.0 * n * m["h"] * m["dh"] * BF16
    flops = 4.0 * m["h"] * m["dh"] * (ctx + n)
    return m["layers"] * flops, kv + m["layers"] * qo


def flash_prefill_work(cfg: Dict, tokens: int):
    """(FLOPs, bytes) of the flash kernel over every layer for one plain
    prefill of ``tokens``: causal QK^T and PV, reading Q, K and V once and
    writing O."""
    m = dims(cfg)
    flops = 4.0 * m["h"] * m["dh"] * tokens * (tokens + 1) / 2
    byts = (2 * m["h"] + 2 * m["kvh"]) * tokens * m["dh"] * BF16
    return m["layers"] * flops, m["layers"] * float(byts)


def roofline_seconds(flops: float, byts: float, pk: Dict[str, float]):
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / pk["bf16_flops"], byts / pk["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
