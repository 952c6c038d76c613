"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512 + MoE (arXiv:2405.04434).

The published DeepSeek-V2-Lite (hf:deepseek-ai/DeepSeek-V2-Lite,
config.json): 27 layers at d_model 2048, 16 heads. Layer 0 is dense
(``first_k_dense_replace`` 1, SwiGLU width 10,944); layers 1–26 each have
64 routed experts of width 1,408, top-6 by softmax with the gates not
renormalized (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), and
2 shared experts. MLA: no q compression, kv_lora 512, qk_nope 128,
qk_rope 64, v_head 128 ⇒ decode cache = 576 values/token/layer. The rope
part uses YaRN (factor 40 over 4,096 original positions, mscale_all_dim
0.707). How many routed experts a chip holds is not part of the model:
``expert_parallel`` (``serve --expert-parallel``) sets it.
"""

from .base import ModelConfig, Yarn, replace

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    vocab_size=102_400,
    attention="mla",
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,            # qk_nope + qk_rope (for bookkeeping)
    rope_scaling=Yarn(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    d_ff=10_944,
    first_k_dense=1,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    norm_eps=1e-6,
    sharding_overrides=(("experts", "model"), ("moe_ff", None)),
)

REDUCED = replace(
    CONFIG, name="deepseek-v2-reduced", num_layers=3, d_model=128,
    vocab_size=512, num_heads=4, num_kv_heads=4, kv_lora_rank=32,
    qk_rope_dim=16, qk_nope_dim=32, v_head_dim=32, head_dim=48, d_ff=256,
    num_experts=8, num_shared_experts=1, top_k=2, moe_d_ff=64,
)
