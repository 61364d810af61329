"""Pallas TPU kernels for the MX quantization hot-spots.

  mx_quant.py      — fused block-scale quantize-dequantize (VPU, VMEM-tiled)
  mx_matmul.py     — forward MX GEMM, fp32 accum (MXU): quantize-on-load for
                     bf16 weights (training), dequantize-on-load for
                     weights packed at rest (MXWeight, serving)
  mx_matmul_bwd.py — backward MX GEMMs: dgrad + wgrad, quantize-on-load
  mx_attention.py  — flash attention: fwd (online softmax, tile-skipping),
                     dgrad pair (dQ + dK/dV), decode (Tq=1) — both BMMs in
                     MX precision, quantize-on-load
  ops.py           — jit'd wrappers (rank/axis handling, interpret fallback)
  ref.py           — pure-jnp oracles (delegate to the validated numerics core)

All three GEMMs of a quantized training step, with each operand MX-blocked
along that GEMM's own contraction axis (paper App. A / qconfig.py):

      forward  : y  = Q[a_fwd](x) @ Q[w_fwd](W)       blocks along K
      dgrad    : dx = Q[g_bwd](dy) @ Q[w_bwd](W)^T    blocks along N
      wgrad    : dW = Q[a_bwd](x)^T @ Q[g_bwd](dy)    blocks along T

plus the attention pair (QK^T blocks along d, PV along the kv axis).

`repro.core.qlinear.mx_contract` dispatches here (custom VJPs), so models,
the serve engine, and the training loop run fully fused quantized steps on
TPU; off-TPU the same kernels run under the Pallas interpreter for tests
and CI.
"""
from .ops import (mx_attention_decode, mx_attention_decode_paged,
                  mx_flash_attention, mx_flash_attention_bwd, mx_matmul,
                  mx_matmul_dgrad, mx_matmul_wgrad, mx_quantize)
from .ref import (gather_pages, mx_attention_decode_paged_ref,
                  mx_attention_decode_ref, mx_flash_attention_bwd_ref,
                  mx_flash_attention_ref, mx_matmul_dgrad_ref, mx_matmul_ref,
                  mx_matmul_wgrad_ref, mx_quantize_ref)

__all__ = [
    "mx_matmul", "mx_matmul_dgrad", "mx_matmul_wgrad", "mx_quantize",
    "mx_flash_attention", "mx_flash_attention_bwd", "mx_attention_decode",
    "mx_attention_decode_paged",
    "mx_matmul_ref", "mx_matmul_dgrad_ref", "mx_matmul_wgrad_ref",
    "mx_quantize_ref", "mx_flash_attention_ref", "mx_flash_attention_bwd_ref",
    "mx_attention_decode_ref", "mx_attention_decode_paged_ref",
    "gather_pages",
]
