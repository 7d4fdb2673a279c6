"""deepseek-v2-lite [moe] — latent attention (MLA, no q-LoRA), one dense
layer then 26 layers of 64 routed experts (top-6, softmax, gates not
renormalised) plus 2 shared experts.  [arXiv:2405.04434 §2.1–2.2;
hf:deepseek-ai/DeepSeek-V2-Lite config.json]"""
from .base import ArchConfig, YarnScaling

CONFIG = ArchConfig(
    arch_id="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10_944, vocab=102_400, head_dim=192,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10_000.0,
    rope_yarn=YarnScaling(factor=40.0, original_max_position=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
    n_experts=64, top_k=6, expert_d_ff=1408, first_k_dense=1,
    shared_expert=True, shared_expert_d_ff=2816,
    norm_topk_prob=False, routed_scaling_factor=1.0,
    # the published model whole: every expert held, dropless
    experts_held=(0, 64),
)
