"""deepseek-v2-lite-16b — MLA (kv_lora 512, YaRN RoPE) + one dense layer,
then 64-routed/2-shared top-6 MoE [arXiv:2405.04434; the published
config.json of deepseek-ai/DeepSeek-V2-Lite]."""
from repro.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", arch_type="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=10944, vocab=102400, norm_eps=1e-6, rope_theta=10000.0,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, num_shared_experts=2, top_k=6,
                  d_ff_expert=1408, norm_topk_prob=False,
                  routed_scaling_factor=1.0, dropless=True,
                  aux_loss_weight=0.001),
    yarn=YarnConfig(factor=40.0, beta_fast=32.0, beta_slow=1.0,
                    original_max_position_embeddings=4096, mscale=0.707,
                    mscale_all_dim=0.707),
    source="arXiv:2405.04434",
)
