"""DeepSeek-V2-Lite: multi-head latent attention (kv_lora_rank 512, no q
LoRA, YaRN rotary on 64 dims), one leading dense SwiGLU layer of 10944,
then 26 expert layers of 64 routed experts of 1408 (softmax, greedy top-6,
weights not renormalised) and 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from .base import LatentMoEConfig

CONFIG = LatentMoEConfig(
    name="deepseek_v2_lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400, d_head=192, rope_theta=10000.0, norm_eps=1e-6,
    n_experts=64, top_k=6, n_shared_experts=2,
    n_dense_layers=1, dense_d_ff=10944,
    n_router_experts=64, expert_offset=0,
    norm_topk_prob=False, routed_scaling_factor=1.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling=(("factor", 40.0), ("original_max_position_embeddings", 4096),
                  ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 0.707),
                  ("mscale_all_dim", 0.707)),
    max_seq=163840, source="hf:deepseek-ai/DeepSeek-V2-Lite",
)

# 16 experts (a smoke share holds 4 of them), every mechanism kept
SMOKE = CONFIG.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                       d_ff=32, vocab=256, d_head=24, n_experts=16, top_k=4,
                       n_dense_layers=1, dense_d_ff=96, n_router_experts=16,
                       kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16,
                       attn_q_chunk=16, attn_kv_chunk=32)
