"""deepseek-v2-lite [moe] — hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434.
27L d_model=2048 16H, MLA (kv_lora_rank 512, qk 128 + rope 64, v 128, no
q-LoRA), YaRN x40; layer 0 dense SwiGLU 10944, layers 1-26 DeepSeekMoE:
64 routed experts of 1408, top-6 softmax gates (not renormalised), 2
shared experts; vocab 102400."""
from repro.configs.base import ModelConfig, Yarn, register

FULL = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    activation="silu", norm="rmsnorm", pos="rope", rope_theta=10000.0,
    rope_yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    num_experts=64, experts_per_token=6, moe_d_ff=1408, shared_experts=2,
    first_dense_layers=1, norm_topk=False,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
)

SMOKE = FULL.replace(
    name="deepseek-v2-lite-smoke", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, d_ff=96, vocab_size=256, num_experts=8,
    experts_per_token=3, moe_d_ff=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
)

register(FULL, SMOKE, skip_shapes=("long_500k",))
