"""Moonlight-16B-A3B (moonshot-v1-16b-a3b): DeepSeek-V3 blocks at 16B.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B (config.json,
``model_type`` deepseek_v3). 27 layers of hidden 2048 and 16 heads:

- latent attention (MLA) without q-LoRA: ``kv_lora_rank`` 512,
  ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128,
  ``rope_theta`` 50000, no rope scaling;
- ``first_k_dense_replace`` 1: layer 0 has a dense SwiGLU FFN of 11264;
- 26 MoE layers: 64 routed experts of 1408, top-6, plus 2 shared experts
  (one SwiGLU MLP of 2 x 1408);
- routing ``scoring_func`` sigmoid, ``topk_method`` noaux_tc (the
  ``e_score_correction_bias`` picks the experts but does not weight them),
  ``norm_topk_prob`` true, ``routed_scaling_factor`` 2.446, ``n_group`` =
  ``topk_group`` = 1 (so group-limited routing chooses among all 64);
- RMSNorm eps 1e-5, vocabulary 163,840, untied head, bf16 weights.

The full model holds about 16.0e9 parameters, 2.9e9 active per token.
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    activation="silu",
    gated_mlp=True,
    pos_emb="rope",
    rope_theta=5e4,
    norm="rmsnorm",
    norm_eps=1e-5,
    block_pattern="moe",
    moe=MoEConfig(num_experts=64, top_k=6, router="sigmoid_noaux",
                  norm_topk_prob=True, routed_scaling_factor=2.446,
                  n_shared_experts=2, dispatch="grouped"),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    first_k_dense=1,
    dense_d_ff=11264,
    max_seq_len=8192,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)
