"""moonshot-v1-16b-a3b  [moe]  (Moonlight-16B-A3B, ``model_type`` deepseek_v3).

27L d_model=2048 16H MLA (kv_lora=512, no q_lora: queries by one
projection, nope=128, rope=64, v_head=128), first layer dense (d_ff=11264),
then 26 MoE layers of 64 routed experts (d_ff=1408) top-6 + 2 shared;
DeepSeek-V3 router (sigmoid scores, a selection-only correction bias,
gates normalized over the 6 and scaled by 2.446, one group); vocab=163840,
untied head, rope_theta=5e4, no rope scaling
[hf:moonshotai/Moonlight-16B-A3B config.json].
"""
from repro.models import LMConfig
from .base import register


def full() -> LMConfig:
    return LMConfig(
        name="moonshot-v1-16b-a3b", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_head=128, d_ff=11264, vocab=163840, act="swiglu",
        norm="rmsnorm", mla=True, q_lora=0, kv_lora=512, nope_dim=128,
        rope_dim=64, v_head=128, n_experts=64, top_k=6, n_shared=2,
        moe_dff=1408, first_dense=1, router="sigmoid", routed_scale=2.446,
        rope_theta=5e4,
    )


def smoke() -> LMConfig:
    return LMConfig(
        name="moonshot-v1-16b-a3b-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_head=16, d_ff=128, vocab=512, act="swiglu",
        norm="rmsnorm", mla=True, q_lora=0, kv_lora=32, nope_dim=16,
        rope_dim=8, v_head=16, n_experts=8, top_k=2, n_shared=1, moe_dff=64,
        first_dense=1, router="sigmoid", routed_scale=2.446, loss_chunk=128,
    )


register("moonshot-v1-16b-a3b", full, smoke)
