"""DeepSeek-V2 236B (21B active) [arXiv:2405.04434].

60L, d_model 5120, 128 heads, MLA (kv_lora 512, q_lora 1536), MoE with
160 routed experts top-6 + 2 shared, d_expert 1536; layer 0 dense.
Routed outputs scaled by 16, YaRN rope, as its config.json gives; its
group-limited top-k is taken as plain greedy top-k.
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    arch_type="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    d_ff=12288,          # dense layer-0 FFN width
    vocab=102400,
    block="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_routed=160, n_shared=2, top_k=6, d_expert=1536,
                  first_dense_layers=1, routed_scaling=16.0),
    rope_scaling=RopeScaling(),
    source="arXiv:2405.04434 (DeepSeek-V2)",
)
