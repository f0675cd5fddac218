"""deepseek-v2-236b [moe] — MLA kv_lora=512 with latent RMSNorms and YaRN
RoPE, 2 shared + 160 routed top-6 over 8 groups (top 3), routed scale 16
[arXiv:2405.04434; hf]."""
from repro.configs.registry import register
from repro.models.common import ModelConfig, YaRN

# rope_scaling of the published config: factor 40 over 4096 positions
YARN = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


@register("deepseek-v2-236b")
def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b",
        n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128,
        d_ff=12288,                      # dense-layer FFN (first layer)
        vocab=102400,
        attn="mla", kv_lora_rank=512, q_lora_rank=1536,
        rope_head_dim=64, nope_head_dim=128, rope_scaling=YARN,
        n_experts=160, n_shared_experts=2, moe_top_k=6, moe_d_ff=1536,
        n_dense_layers=1,
        moe_n_group=8, moe_topk_group=3, moe_norm_topk=False,
        moe_routed_scale=16.0,
        tie_embeddings=False,
    )


@register("deepseek-v2-236b-smoke")
def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b-smoke",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab=256,
        attn="mla", kv_lora_rank=32, q_lora_rank=48,
        rope_head_dim=8, nope_head_dim=16, rope_scaling=YARN,
        n_experts=8, n_shared_experts=2, moe_top_k=2, moe_d_ff=32,
        n_dense_layers=1,
        moe_n_group=4, moe_topk_group=2, moe_norm_topk=False,
        moe_routed_scale=16.0,
        tie_embeddings=False,
    )
