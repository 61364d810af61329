"""Shared layers: MX-quantized dense, norms with MX-quantized affine, RoPE.

Layernorm handling follows the paper's App. A exactly: the *vector* ops
(mean/variance reductions, residual adds) run in bf16/fp32, while the
affine scale is MX-quantized per ``qcfg.ln_fmt`` — these tightly clustered
log-normal parameters are the paper's §6.1 instability culprit, so their
quantization is a first-class, toggleable feature.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, mx_contract, quantize_mx
from repro.core.mx import MXWeight

PARAM_DTYPE = jnp.float32     # master copies live in the optimizer
COMPUTE_DTYPE = jnp.bfloat16

__all__ = ["dense_init", "qdense", "norm_init", "apply_norm", "embed_init",
           "embed_lookup", "rope", "conv_tail", "kaiming_uniform",
           "trunc_normal", "PARAM_DTYPE", "COMPUTE_DTYPE"]


def conv_tail(x: jax.Array, width: int) -> jax.Array:
    """Last ``width`` inputs of a causal conv stream (B, T, d), zero-padded
    on the left for T < width — the decode carry a depthwise conv of
    width ``width+1`` holds after consuming the full sequence."""
    zeros = jnp.zeros((x.shape[0], width, x.shape[-1]), x.dtype)
    return jnp.concatenate([zeros, x], 1)[:, -width:]


def kaiming_uniform(key, shape, fan_in: Optional[int] = None,
                    gain: float = 1.0, dtype=PARAM_DTYPE):
    """PyTorch-default init (paper's proxy baseline, App. B)."""
    fan_in = fan_in or shape[-2] if len(shape) >= 2 else shape[-1]
    bound = gain / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def trunc_normal(key, shape, std: float, dtype=PARAM_DTYPE):
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape, dtype)


def dense_init(key, d_in: int, d_out: int, std: Optional[float] = None,
               bias: bool = False, init: str = "trunc_normal"):
    if init == "kaiming_uniform":
        w = kaiming_uniform(key, (d_in, d_out), fan_in=d_in)
    elif init == "xavier_lowgain":  # paper App. B variant (gain=0.5)
        std_x = 0.5 * math.sqrt(2.0 / (d_in + d_out))
        w = jax.random.normal(key, (d_in, d_out), PARAM_DTYPE) * std_x
    else:
        w = trunc_normal(key, (d_in, d_out), std or 1.0 / math.sqrt(d_in))
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), PARAM_DTYPE)
    return p


def qdense(p, x: jax.Array, qcfg: QuantConfig) -> jax.Array:
    """MX-quantized dense layer. Bias add stays bf16 (vector op).

    The projection runs through the "dense" custom VJP of `mx_contract`,
    so its forward, dgrad, and wgrad GEMMs each hit the fused
    quantize-on-load Pallas kernels in their per-pass formats (a_fwd/w_fwd,
    g_bwd/w_bwd, a_bwd/g_bwd) whenever ``qcfg`` is kernel-eligible.  A
    weight packed at rest (``MXWeight``, see ``pack_serving_weights``) is
    passed as it is."""
    w = p["w"]
    if not isinstance(w, MXWeight):
        w = w.astype(x.dtype)
    y = mx_contract(x, w, qcfg, kind="dense")
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def norm_init(d: int, kind: str = "rmsnorm"):
    p = {"scale": jnp.ones((d,), PARAM_DTYPE)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), PARAM_DTYPE)
    return p


def apply_norm(p, x: jax.Array, qcfg: QuantConfig, kind: str = "rmsnorm",
               eps: float = 1e-5) -> jax.Array:
    """Norm with MX-quantized affine parameters (paper §6.1).

    The normalized activations and the affine scale are both quantized when
    ``qcfg.ln_fmt`` is set (full-quant baseline); mitigations set
    ``ln_fmt=None`` which makes this a plain bf16 norm.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xn = xf * jax.lax.rsqrt(var + eps)
    scale = p["scale"].astype(jnp.float32)
    if qcfg.ln_fmt is not None:
        scale = quantize_mx(scale, qcfg.ln_fmt, axis=-1, block=qcfg.block,
                            scale_mode=qcfg.scale_mode)
        xn = quantize_mx(xn, qcfg.ln_fmt, axis=-1, block=qcfg.block,
                         scale_mode=qcfg.scale_mode)
    y = xn * scale
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dtype)


def embed_init(key, vocab: int, d: int):
    return {"table": trunc_normal(key, (vocab, d), 1.0 / math.sqrt(d))}


def embed_lookup(p, ids: jax.Array) -> jax.Array:
    return p["table"].astype(COMPUTE_DTYPE)[ids]


def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotary embedding over the last axis. x: (..., T, ..., d_head) with
    positions broadcastable to x's T axis; we require x: (B, T, H|G.., d)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32)
                    / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B?, T, half)
    # insert singleton head axes between T and d for broadcasting.
    extra = x.ndim - positions.ndim - 1
    ang = ang.reshape(ang.shape[:-1] + (1,) * extra + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)
