"""DeepSeek-V3 671B: MLA + 256-expert top-8 MoE (1 shared), 3 leading dense
layers, MTP [arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json].

``EP32`` is what one chip of an expert-parallel decode deployment holds of
the same model: 32 chips share each MoE layer, 8 routed experts each (this
one holds experts 0-7); attention, the shared expert and the dense layers
are replicated on every chip; the vocabulary is split over 8 chips (this
one holds 16,160 of the 129,280 rows). Depth is cut to the 3 dense layers
and 4 MoE layers; the 54 left out would lie on further pipeline stages.
The MTP module is not served (the engine has no speculative decoding).
"""
from .base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,
    d_ff=18432,  # intermediate_size: the dense layers' FFN width
    vocab_size=129280,
    rope_theta=1e4,
    yarn=YarnConfig(factor=40.0, beta_fast=32.0, beta_slow=1.0, original_max_position=4096),
    mla=MLAConfig(
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        n_experts=256,
        top_k=8,
        expert_ff=2048,
        shared_ff=2048,  # 1 shared expert
        first_dense=3,
        dense_ff=18432,
        scoring="sigmoid",  # noaux_tc: sigmoid + selection bias, group-limited
        norm_topk_prob=True,
        n_group=8,
        topk_group=4,
        routed_scaling_factor=2.5,
    ),
    mtp=True,
    source="arXiv:2412.19437 (61L d7168 128H MLA, 256e top-8 + 1 shared, MTP)",
)

EP32 = CONFIG.replace(
    name="deepseek-v3-ep32",
    n_layers=7,  # the 3 dense layers and 4 MoE layers
    vocab_size=129280 // 8,  # one chip's slice of 8 ...
    vocab_padded=0,  # ... padded to 16,384 rows
    moe=MoEConfig(**{**CONFIG.moe.__dict__, "held_first": 0, "n_held": 8}),
    mtp=False,
    source="hf:deepseek-ai/DeepSeek-V3, one chip of EP32: 7 of 61 layers, "
    "experts 0-7 of 256, 16,160 of 129,280 vocabulary rows",
)
