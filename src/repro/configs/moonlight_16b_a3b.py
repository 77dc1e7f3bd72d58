"""Moonlight-16B-A3B [moonshotai/Moonlight-16B-A3B config.json, model_type
deepseek_v3]: 27 layers, the first with a dense SwiGLU FFN (11264), the
other 26 DeepSeek-V3 expert layers — 64 routed experts of width 1408, top-6
by sigmoid score + correction bias (noaux_tc, one group), renormalized and
scaled by 2.446, and 2 shared experts; latent attention (MLA) with 16 heads,
no query latent, kv_lora_rank 512, query/key heads of 128 + 64 (rotated on
interleaved pairs), values of 128; RMSNorm 1e-5, RoPE theta 50000, context
8192, vocabulary 163840, untied head. The client holds the embedding and the
dense first layer, so the cut falls before the first expert layer."""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight_16b_a3b", family="moe",
    num_layers=27, d_model=2048, vocab_size=163840,
    num_heads=16, num_kv_heads=16, head_dim=192,
    attn_kind="mla", kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
    rope_theta=50_000.0,
    d_ff=11264, mlp_type="swiglu", norm_type="rmsnorm", norm_eps=1e-5,
    num_experts=64, experts_per_token=6, moe_layer="dropless",
    moe_d_ff=1408, num_shared_experts=2, routed_scaling=2.446,
    first_dense_layers=1, cut_periods=1,
    dtype="bfloat16", param_dtype="bfloat16", optimizer="adam",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
)

SMOKE_CONFIG = ArchConfig(
    name="moonlight_16b_a3b_smoke", family="moe",
    num_layers=3, d_model=256, vocab_size=512,
    num_heads=4, num_kv_heads=4, head_dim=48,
    attn_kind="mla", kv_lora_rank=64, qk_rope_dim=16, v_head_dim=32,
    rope_theta=50_000.0,
    d_ff=512, mlp_type="swiglu", norm_type="rmsnorm", norm_eps=1e-5,
    num_experts=4, experts_per_token=2, moe_layer="dropless",
    moe_d_ff=128, experts_held=2, num_shared_experts=1, routed_scaling=2.446,
    first_dense_layers=1, cut_periods=1, vocab_pad_to=64, remat=False,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
)
