"""DeepSeek-V2 236B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2
config.json] — MLA (kv_lora 512, q_lora 1536, rope 64 with YaRN x40), a
leading dense layer (d_ff 12288), then 59 MoE layers: 160 routed experts of
1536, top-6 by group-limited greedy routing (8 groups, 3 per token), softmax
gates scaled by 16 and not renormalised, 2 shared experts.
"""
from repro.configs.base import BlockDesc, ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    n_dense_layers=1,      # first_k_dense_replace
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,        # nominal; MLA replaces GQA entirely
    d_ff=12288,            # the dense layer's intermediate_size
    vocab_size=102400,
    head_dim=128,
    rope="1d",             # decoupled rope on the qk_rope_dim slice (MLA)
    rope_theta=10_000.0,
    norm="rmsnorm",
    act="silu",
    n_experts=160,
    n_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1536,
    n_group=8,
    topk_group=3,
    routed_scaling_factor=16.0,
    norm_topk_prob=False,
    mla=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_ctx=4096,
    yarn_mscale=0.707,     # mscale_all_dim; mscale is equal, so cos/sin x 1
    period=(BlockDesc("attn", "moe"),),
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2",
)
