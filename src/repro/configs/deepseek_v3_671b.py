"""deepseek-v3-671b [moe] — MLA with YaRN, 1 shared + 256 routed experts
top-8 by sigmoid group-limited routing, MTP.

61 layers (first 3 dense), d_model=7168, 128 heads, expert d_ff=2048,
vocab=129280.  Routing: sigmoid scores, a selection-only bias per expert
(``noaux_tc``), 8 groups of 32 experts of which the top 4 (by the sum of
each group's two best biased scores) are kept, top-8 experts among them,
weights normalised and scaled by 2.5.  [arXiv:2412.19437;
huggingface.co/deepseek-ai/DeepSeek-V3 config.json]
"""

from repro.configs.base import (MLAConfig, MoEConfig, ModelConfig,
                                YarnConfig, register)

CONFIG = register(ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    source="arXiv:2412.19437",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,             # MLA: latent cache shared by all heads
    head_dim=128,
    d_ff=2048,                    # expert intermediate size
    vocab_size=129280,
    attn_kind="mla",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    norm_kind="rmsnorm",
    norm_eps=1e-6,
    act="swiglu",
    max_position=163840,
    mtp=True,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(num_experts=256, top_k=8, d_ff_expert=2048,
                  num_shared_experts=1, first_k_dense=3, d_ff_dense=18432,
                  router_aux_weight=1e-3, norm_topk_prob=True,
                  scoring="sigmoid", n_group=8, topk_group=4,
                  routed_scaling=2.5),
))
