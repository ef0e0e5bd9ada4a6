"""moonshot-v1-16b-a3b [moe]: Moonlight-16B-A3B as published (a
deepseek_v3 layout): 27L d_model=2048; MLA 16H with no query compression
(kv_lora 512, nope 128 + rope 64, v 128, rope_theta 50000); layer 0
dense (d_ff 11264), layers 1-26 MoE 64e top-6 (d_expert 1408) + 2
shared, sigmoid router with a selection-only bias, top-k weights
normalized then scaled by 2.446; rmsnorm eps 1e-5; untied vocab=163840;
15.96B parameters.  [hf:moonshotai/Moonlight-16B-A3B]"""
from repro.models.config import BlockSpec, MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonshot-v1-16b-a3b",
        d_model=2048, vocab_size=163840, d_ff=11264,
        prefix=(BlockSpec("mla", "mlp"),),
        period=(BlockSpec("mla", "moe"),), n_periods=26,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, n_heads=16, rope_theta=50000.0),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      router="sigmoid", route_scale=2.446, norm_topk=True),
        mlp_act="silu", norm_eps=1e-5, tie_embeddings=False,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="moonshot-v1-16b-a3b-smoke",
        d_model=64, vocab_size=277, d_ff=160,
        prefix=(BlockSpec("mla", "mlp"),),
        period=(BlockSpec("mla", "moe"),), n_periods=2,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16,
                      qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16, n_heads=4, rope_theta=50000.0),
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=48, n_shared=2,
                      router="sigmoid", route_scale=2.446, norm_topk=True),
        mlp_act="silu", norm_eps=1e-5, tie_embeddings=False,
    )
