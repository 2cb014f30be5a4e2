"""deepseek-v3-671b [moe]: MLA + fine-grained MoE (1 shared + 256 routed, top-8).

61L d_model=7168 128H d_ff=2048(expert) vocab=129280  [arXiv:2412.19437]
MLA: q_lora=1536, kv_lora=512, qk_nope=128, qk_rope=64, v_head=128.
Dense d_ff (first 3 layers) = 18432; the shared expert is 2048 wide.
Router: sigmoid scores with a per-expert correction bias (noaux_tc), the
best 4 of 8 expert groups, top-8 renormalised and scaled by 2.5. RoPE with
YaRN (factor 40 over 4096 positions) in the 64 rope dims.
MTP (multi-token prediction) head is optional and off for the assigned shapes.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,          # MLA: per-head KV reconstructed from latent
    d_ff=18432,                # dense-layer hidden size
    vocab_size=129280,
    attention_kind="full",
    use_rope=True,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                             mscale_all_dim=1.0),
    mla=MLAConfig(
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=256,
        top_k=8,
        d_ff_expert=2048,
        num_shared_experts=1,
        d_ff_shared=2048,
        capacity_factor=1.25,
        first_dense_layers=3,
        scoring="sigmoid",
        n_group=8,
        topk_group=4,
        routed_scaling_factor=2.5,
    ),
    norm="rmsnorm",
    act="silu",
    use_glu=True,
    param_dtype="bfloat16",
    moment_dtype="bfloat16",   # >100B: bf16 moments + fp32 master to fit 16GB/chip
    sharding_plan="fsdp_tp",
    remat_policy="full",
)

SMOKE_CONFIG = CONFIG.replace(
    num_layers=4,
    d_model=128,
    num_heads=4,
    num_kv_heads=4,
    d_ff=256,
    vocab_size=512,
    mla=MLAConfig(q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=64, num_shared_experts=1,
                  d_ff_shared=64, first_dense_layers=1, scoring="sigmoid",
                  n_group=4, topk_group=2, routed_scaling_factor=2.5),
    param_dtype="float32",
    moment_dtype="float32",
    sharding_plan="tp",
    remat_policy="none",
    scan_layers=False,
)
