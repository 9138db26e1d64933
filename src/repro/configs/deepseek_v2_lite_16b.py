"""deepseek-v2-lite-16b [moe] — 27L d=2048 16H ff(expert)=1408 vocab=102400.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json] — MLA with
kv_lora=512, no query compression (q_lora_rank null), decoupled RoPE (64-dim
key shared by the heads) under YaRN (factor 40 over 4,096 original positions,
beta 32/1, mscale = mscale_all_dim = 0.707), MoE with 64 routed experts top-6
by softmax without renormalising the six gates (norm_topk_prob false) plus 2
shared experts, first layer dense (ff 10944).  This is the Lite model: the
full DeepSeek-V2 has 160 routed experts.
"""

from repro.models.common import YaRN
from repro.models.transformer import MLAConfig, MoEConfig, TransformerConfig

ARCH_ID = "deepseek-v2-lite-16b"

ROPE_SCALING = YaRN(factor=40.0, original_max_position=4_096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def config() -> TransformerConfig:
    return TransformerConfig(
        name=ARCH_ID, vocab=102_400, d_model=2_048, n_layers=27,
        n_heads=16, n_kv=16, d_ff=10_944,
        act="silu", glu=True, norm="rms",
        mla=MLAConfig(kv_lora=512, rope_head_dim=64, nope_head_dim=128,
                      v_head_dim=128, rope_scaling=ROPE_SCALING),
        moe=MoEConfig(num_experts=64, top_k=6, d_expert=1_408, num_shared=2,
                      first_dense_layers=1, dense_d_ff=10_944,
                      router_scale=False),
    )


def reduced() -> TransformerConfig:
    return TransformerConfig(
        name=ARCH_ID + "-reduced", vocab=512, d_model=64, n_layers=3,
        n_heads=4, n_kv=4, d_ff=256,
        act="silu", glu=True, norm="rms",
        mla=MLAConfig(kv_lora=32, rope_head_dim=8, nope_head_dim=16,
                      v_head_dim=16, rope_scaling=ROPE_SCALING),
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=32, num_shared=1,
                      first_dense_layers=1, dense_d_ff=256,
                      router_scale=False),
    )
