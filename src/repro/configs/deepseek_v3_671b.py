"""DeepSeek-V3 (671B total / 37B active) [arXiv:2412.19437].

MLA (q_lora=1536, kv_lora=512, qk 128 nope + 64 rope, v=128), 61 layers,
d_model=7168, 128 heads, YaRN RoPE (factor 40 over 4,096 positions, its
mscale squared on the softmax scale). First 3 layers dense
(d_ff=18432); 58 MoE layers with 256 routed experts (top-8 within the
best 4 of 8 groups, sigmoid router + aux-free bias balancing, weights
scaled by 2.5) + 1 shared expert, expert d_ff=2048. vocab=129280,
RMSNorm eps 1e-6. One-depth MTP head. Values from the published
``config.json`` (huggingface.co/deepseek-ai/DeepSeek-V3).
"""
from repro.models.config import (
    AttnSpec, BlockSpec, FfnSpec, ModelConfig, YarnSpec,
)

_MLA = AttnSpec(kind="mla", n_heads=128, head_dim=192, q_lora_rank=1_536,
                kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                v_head_dim=128, rope_theta=10_000.0, n_kv_heads=128,
                rope_scaling=YarnSpec(factor=40.0, original_max_position=4096,
                                      beta_fast=32.0, beta_slow=1.0,
                                      mscale=1.0, mscale_all_dim=1.0))
_DENSE = FfnSpec(kind="dense", d_ff=18_432, activation="silu_glu")
_MOE = FfnSpec(kind="moe", d_ff=18_432, activation="silu_glu",
               n_experts=256, n_shared=1, top_k=8, d_ff_expert=2_048,
               capacity_factor=1.25, router="sigmoid", n_group=8,
               topk_group=4, routed_scaling_factor=2.5)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b",
        d_model=7_168,
        vocab_size=129_280,
        blocks=(
            BlockSpec(repeat=3, mixer="attn", attn=_MLA, ffn=_DENSE),
            BlockSpec(repeat=58, mixer="attn", attn=_MLA, ffn=_MOE),
        ),
        tie_embeddings=False,
        rms_eps=1e-6,
        mtp_depth=1,
        param_dtype="bfloat16",
        activation_dtype="bfloat16",
        fsdp=True,
    )


def smoke_config() -> ModelConfig:
    mla = AttnSpec(kind="mla", n_heads=4, head_dim=48, q_lora_rank=48,
                   kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                   v_head_dim=32, n_kv_heads=4,
                   rope_scaling=YarnSpec(factor=40.0,
                                         original_max_position=16))
    dense = FfnSpec(kind="dense", d_ff=256, activation="silu_glu")
    moe = FfnSpec(kind="moe", d_ff=256, activation="silu_glu",
                  n_experts=8, n_shared=1, top_k=2, d_ff_expert=64,
                  router="sigmoid", n_group=4, topk_group=2,
                  routed_scaling_factor=2.5)
    return ModelConfig(
        name="deepseek-v3-smoke",
        d_model=128,
        vocab_size=512,
        blocks=(
            BlockSpec(repeat=1, mixer="attn", attn=mla, ffn=dense),
            BlockSpec(repeat=2, mixer="attn", attn=mla, ffn=moe),
        ),
        tie_embeddings=False,
        rms_eps=1e-6,
        mtp_depth=1,
        remat=False,
    )
