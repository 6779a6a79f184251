"""moonlight-16b-a3b [moe]: 27L, d=2048, 16H, v=163840, context 8192.

The DeepSeek-V3 block (``model_type`` ``deepseek_v3``): multi-head latent
attention with no query low rank (``kv_lora_rank`` 512, nope/rope/value
head dims 128/64/128, rope theta 50000), layer 0 dense (d_ff 11264) and
layers 1-26 MoE: 64 routed experts of width 1408, top-6, 2 shared
experts, sigmoid scores with a selection bias (``noaux_tc``, one group),
top-k gates normalised and scaled by 2.446.  Untied head, RMSNorm eps
1e-5, weights in bfloat16.
[hf:moonshotai/Moonlight-16B-A3B config.json]
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, register

FULL = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab_size=163840, rope_theta=50000.0, norm_eps=1e-5,
    layer_pattern=("M",),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
                  qk_rope_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  n_shared_experts=2, first_dense_layers=1,
                  scoring="sigmoid", selection_bias=True,
                  routed_scale=2.446),
    tie_embeddings=False, param_dtype="bfloat16",
)

SMOKE = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=256, rope_theta=50000.0, norm_eps=1e-5,
    layer_pattern=("M",),
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, qk_nope_dim=16,
                  qk_rope_dim=8, v_head_dim=16),
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                  n_shared_experts=2, first_dense_layers=1,
                  scoring="sigmoid", selection_bias=True,
                  routed_scale=2.446),
    tie_embeddings=False, attn_chunk=32,
)

register(FULL, SMOKE)
