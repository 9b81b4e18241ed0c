"""qwen3-moe-235b-a22b [moe]: 94L d_model=4096 64H (kv=4) head_dim=128
d_ff=1536 per expert, vocab=151936 untied, MoE 128 routed top-8, no shared
experts (hf:Qwen/Qwen3-235B-A22B config.json; Qwen3 Technical Report,
arXiv:2505.09388).

head_dim=128 (Qwen3 decouples head_dim from d_model/num_heads).  No QKV
bias (``attention_bias`` false).  QK-norm: an RMSNorm over head_dim with
learned scales on every query and key head, before RoPE.  The router is a
softmax over all 128 experts in f32 whose top-8 weights are renormalised
to sum to 1 (``norm_topk_prob`` true).  RoPE theta 1e6, RMSNorm eps 1e-6.
The EP stress cell: 128 experts sharded over the model axis.  Full
attention: long_500k skipped.
"""

from repro.configs.base import ArchSpec, LONG_SKIP, register
from repro.models.lm import LMConfig

CONFIG = LMConfig(
    name="qwen3-moe-235b-a22b", family="moe",
    vocab=151936, d_model=4096, n_layers=94,
    num_heads=64, num_kv_heads=4, d_ff=1536, head_dim=128,
    qk_norm=True, rope_theta=1e6,
    moe_experts=128, moe_top_k=8,
    chunk_size=512,
)

SMOKE = LMConfig(
    name="qwen3-moe-235b-a22b-smoke", family="moe",
    vocab=256, d_model=64, n_layers=3,
    num_heads=8, num_kv_heads=2, d_ff=32, head_dim=16,
    qk_norm=True,
    moe_experts=16, moe_top_k=4,
    chunk_size=16,
)

register(ArchSpec(
    arch_id="qwen3-moe-235b-a22b", config=CONFIG, smoke=SMOKE,
    source="hf:Qwen/Qwen3-235B-A22B; arXiv:2505.09388",
    skip_shapes=(LONG_SKIP,),
))
