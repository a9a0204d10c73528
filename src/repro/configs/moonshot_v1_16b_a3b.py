"""moonshot-v1-16b-a3b — Moonlight-16B-A3B, a DeepSeek-V3 block
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3].

27 layers of multi-head latent attention (16 heads, no query low-rank
projection, kv_lora_rank 512, nope/rope/v head dims 128/64/128); layer 0
has a dense SwiGLU FFN of width 11,264 (first_k_dense_replace 1), layers
1-26 route each token to 6 of 64 experts of width 1,408 (sigmoid scores,
top-6 on score + e_score_correction_bias with n_group = topk_group = 1,
norm_topk_prob, routed_scaling_factor 2.446) beside 2 shared experts,
one SwiGLU of width 2 x 1,408.  RMSNorm eps 1e-5, RoPE theta 50,000,
untied embeddings of 163,840 tokens.

Departures from the published modeling code, none of which a model with
random weights can tell apart: RoPE rotates the split halves of the 64
rope dimensions (the repository's layout; DeepSeek's code de-interleaves
pairs first), and the latent's own RMSNorm uses eps 1e-6, the modeling
code's default for it.
"""
from repro.configs.base import ArchConfig, MLASpec, MoESpec, register

CONFIG = register(ArchConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,                    # the leading dense layer
    vocab=163840,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    first_dense=1,
    mla=MLASpec(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, expert_d_ff=1408,
                shared_expert_ff=2816,  # 2 shared experts as one SwiGLU
                scoring="sigmoid", select_bias=True, norm_topk=True,
                routed_scale=2.446),
    param_dtype="bfloat16",
    source="hf:moonshotai/Moonlight-16B-A3B",
))
