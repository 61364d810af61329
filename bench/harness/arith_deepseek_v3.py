"""Operations and bytes the benchmark credits to a DeepSeek-V3-style
decoder (latent attention, a leading dense layer, sigmoid-routed experts
beside shared experts), one chip's share of an expert-parallel layer.

The rules are ``harness/arith.py``'s: a GEMM of (M, K) by (K, N) is
2*M*K*N operations, bytes are operands and results at the smallest size
the precision allows (MX weights at 1 + 1/32 B an element, activations
and results bf16), and a model's FLOPs are those of its forward pass.
What this chip computes for a token, its active FLOPs:

* every projection of latent attention, the dense layer's MLP, the shared
  experts, the router, the head;
* of the routed experts, ``top_k * experts_held / n_experts`` a token and
  MoE layer: the expected share of its top-k that this chip holds (6 x
  8/64 = 0.75 experts; the group's other chips compute the rest);
* attention: decode in the absorbed form over ``ctx`` cached latents,
  2*(kv_lora + rope) a head and position for the scores and 2*kv_lora
  for the context; prefill in the expanded form, T(T+1)/2 causal pairs a
  head at 2*(nope + rope) + 2*v_head.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from .arith import BF16, MX8_PACKED, causal_pairs, gemm_flops, roofline_s


def held(m: dict) -> int:
    return m.get("experts_held") or m["n_experts"]


def mla_projections(m: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of one layer's latent-attention projections."""
    D, H = m["d_model"], m["n_heads"]
    nope, rope, v, kv = m["nope_dim"], m["rope_dim"], m["v_head"], m["kv_lora"]
    return [("w_q", D, H * (nope + rope)), ("w_dkv", D, kv), ("w_kr", D, rope),
            ("w_uk", kv, H * nope), ("w_uv", kv, H * v), ("wo", H * v, D)]


def mlp_projections(m: dict, width: int) -> List[Tuple[str, int, int]]:
    """(name, K, N) of a SwiGLU MLP of hidden ``width``."""
    D = m["d_model"]
    return [("w_up", D, width), ("w_gate", D, width), ("w_down", width, D)]


def expert_projections(m: dict) -> List[Tuple[str, int, int]]:
    return mlp_projections(m, m["moe_dff"])


def _macs(projs) -> int:
    return sum(k * n for _, k, n in projs)


def token_gemm_flops(m: dict) -> float:
    """GEMM FLOPs of one token through every layer and the head, the
    routed experts at this chip's expected share."""
    D, L, Ld = m["d_model"], m["n_layers"], m["first_dense"]
    share = m["top_k"] * held(m) / m["n_experts"]
    mla = _macs(mla_projections(m))
    dense = mla + _macs(mlp_projections(m, m["d_ff"]))
    moe = (mla + _macs(mlp_projections(m, m["n_shared"] * m["moe_dff"]))
           + D * m["n_experts"] + share * _macs(expert_projections(m)))
    return 2.0 * (Ld * dense + (L - Ld) * moe + D * m["vocab"])


def decode_token_flops(m: dict, ctx: int) -> float:
    """Forward FLOPs of one decoded token that attends to ``ctx`` cached
    latents (itself included), attention in the absorbed form."""
    attn = 2.0 * m["n_heads"] * ctx * (2 * m["kv_lora"] + m["rope_dim"])
    return token_gemm_flops(m) + m["n_layers"] * attn


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole causal prompt, attention expanded."""
    per_pair = 2.0 * (m["nope_dim"] + m["rope_dim"] + m["v_head"])
    attn = m["n_heads"] * causal_pairs(prompt_len) * per_pair
    return prompt_len * token_gemm_flops(m) + m["n_layers"] * attn


def expert_gemm_ideal_s(m: dict, held_tokens: Sequence[Sequence[int]],
                        peak: Dict[str, float]) -> float:
    """Roofline time of one step's held-expert GEMMs: for each MoE layer
    and each held expert that got n >= 1 tokens (``held_tokens``, (layers,
    held)), its up, gate and down GEMMs of n rows, weights MX-packed,
    activations and results bf16."""
    total = 0.0
    for layer in held_tokens:
        for n in layer:
            if n <= 0:
                continue
            for _, K, N in expert_projections(m):
                total += roofline_s(gemm_flops(n, K, N),
                                    K * N * MX8_PACKED + n * (K + N) * BF16,
                                    peak)
    return total
