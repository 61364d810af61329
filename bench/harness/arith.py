"""Operations and bytes the benchmark credits to a piece of work.

These count the work the format defines, not what today's kernels do:

* a GEMM of (M, K) by (K, N) is 2*M*K*N operations;
* causal self-attention over T positions is T*(T+1)/2 query-key pairs per
  head, 4*d operations per pair (scores and values);
* bytes are the operands and results at the smallest size the precision
  preset allows at the call's boundary: an MX operand (the weights in
  weight-only MX serving) at its packed size, one byte per E4M3 element
  plus one E8M0 scale per 32 elements; results and bf16 activations at two
  bytes;
* a model's FLOPs are those of its forward pass, with no recomputation.

So a kernel that one day reads fewer bytes can never read above its
roofline.  The model shape is the ``model`` dict of a
configuration file (the program's ``LMConfig`` field names).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16 = 2
MX_BLOCK = 32
MX8_PACKED = 1.0 + 1.0 / MX_BLOCK    # E4M3 element + shared E8M0 scale


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def layer_projections(m: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every projection of one transformer layer."""
    D, H, Hkv, dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    out = [("wq", D, H * dh), ("wk", D, Hkv * dh), ("wv", D, Hkv * dh),
           ("wo", H * dh, D), ("w_up", D, F), ("w_down", F, D)]
    if m.get("act") in ("swiglu", "geglu"):
        out.append(("w_gate", D, F))
    return out


def projections(m: dict) -> List[Tuple[str, int, int]]:
    """Every projection of the model, layers times their count, plus the
    LM head."""
    per = layer_projections(m)
    return per * m["n_layers"] + [("lm_head", m["d_model"], m["vocab"])]


def matmul_params(m: dict) -> int:
    return sum(k * n for _, k, n in projections(m))


def causal_pairs(t: int) -> float:
    return t * (t + 1) / 2.0


def attn_fwd_flops(b: int, t: int, m: dict) -> float:
    """Forward attention mixing of one layer over b causal sequences of t."""
    return 4.0 * b * m["n_heads"] * causal_pairs(t) * m["d_head"]


def decode_token_flops(m: dict, ctx: int) -> float:
    """Forward FLOPs of one decoded token that attends to ``ctx`` positions
    (itself included)."""
    return 2.0 * matmul_params(m) \
        + m["n_layers"] * 4.0 * m["n_heads"] * ctx * m["d_head"]


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole causal prompt."""
    return 2.0 * prompt_len * matmul_params(m) \
        + m["n_layers"] * attn_fwd_flops(1, prompt_len, m)


def decode_gemm_ideal_s(m: dict, rows: int, peak: Dict[str, float]) -> float:
    """Roofline time of one decode step's projection GEMMs for ``rows``
    live rows: weights MX-packed, activations and results bf16."""
    total = 0.0
    for _, K, N in projections(m):
        f = gemm_flops(rows, K, N)
        b = K * N * MX8_PACKED + rows * (K + N) * BF16
        total += roofline_s(f, b, peak)
    return total
