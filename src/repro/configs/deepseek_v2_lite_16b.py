"""DeepSeek-V2-Lite (16B total / 2.4B active) [arXiv:2405.04434].

27L, d_model 2048, 16 heads, MLA (kv_lora 512, no q-lora), MoE with
64 routed experts top-6 + 2 shared, d_expert 1408; layer 0 dense.
YaRN rope (factor 40 over an original 4096 positions), as its config.json
gives.
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,          # dense layer-0 FFN width
    vocab=102400,
    block="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_routed=64, n_shared=2, top_k=6, d_expert=1408,
                  first_dense_layers=1),
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    source="arXiv:2405.04434 (DeepSeek-V2-Lite)",
)
