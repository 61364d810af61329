"""MX-quantized contraction primitives with configurable fwd/bwd quantization.

The paper applies MX quantization "dynamically to the inputs of matrix
multiplication operations ... across both the forward and backward passes,
with results dequantized to a higher precision format after the operation"
(§2.1).  :func:`mx_contract` is the single entry point for every quantized
contraction in the codebase, dispatching on ``kind``:

  "dense"        x (..., K) @ W (K, N) — projections / MLP / LM head.
                 W may be an :class:`~repro.core.mx.MXWeight` (weights
                 packed at rest for serving): forward only, no VJP.
                 Custom VJP with per-GEMM quantization axes:
                   forward : y  = Q[a_fwd](x) · Q[w_fwd](W)   blocks along K
                   dgrad   : dx = Q[g_bwd](dy) · Q[w_bwd](W)ᵀ blocks along N
                   wgrad   : dW = Q[a_bwd](x)ᵀ · Q[g_bwd](dy) blocks along T
  "bmm"          batched per-expert (..., E, M, K) @ (E, K, N) — vmapped
                 "dense" so each expert gets its own block scales.
  "attn_qk",
  "attn_pv"      single attention BMM ``a (..., M, K) @ b (..., K, N)``;
                 both operands quantized with a_fwd along the contraction
                 axis when ``cfg.attn`` (straight-through gradients).
  "flash_attn"   the fused flash-attention contraction pair (QK^T + PV with
                 online softmax between them) on the folded layout
                 q (BH,G,Tq,d) x (k (BH,Tk,d), v (BH,Tk,dv)); masking and
                 tiling come from an :class:`~repro.core.attnspec.AttnSpec`.
                 Custom VJP: the backward recomputes probabilities from the
                 stashed logsumexp (flash dgrad) with the *quantized*
                 scores, while the gradient products themselves stay
                 straight-through — the paper's "BMM backward stays bf16".
  "attn_decode"  the Tq=1 serve-path shape q (BH,G,d) x (k,v) (BH,S,·) with
                 a precomputed (BH,S) validity mask (ring-buffer or global
                 cache semantics live in the mask).
  "attn_decode_paged"
                 the same Tq=1 shape against (N, H, ps, ·) page pools: rhs
                 is the (k_pool, v_pool) pair, ``pages`` the (B, P) int32
                 page table, and ``valid`` a (B, P*ps) per-view mask.  The
                 fused path scalar-prefetches the page table so the gather
                 happens in the kernel's BlockSpec index maps.

Each contraction quantizes its operands along *its own* contraction axis so
the shared scales factor out of every dot product (App. A).  Residuals keep
the un-quantized bf16 tensors, so "forward-only" quantization degrades to
the straight-through estimator the paper's mitigation (2) uses.

Every kind dispatches to the fused Pallas kernels in `repro.kernels`
(quantize-on-load after the HBM→VMEM copy, fp32 VMEM accumulators) whenever
the config is kernel-eligible; the "bump" / "adaptive" scale modes and
kernel-ineligible shapes use the emulation path, which for attention is the
ref.py oracle the kernels are bit-identical to in interpret mode.

Dispatch policy (`fused_gemms_enabled`): fused kernels are on by default on
TPU and off elsewhere — off-TPU the kernels would run under the Pallas
interpreter, which is a correctness device, not a performance path, and the
emulation path is validated bit-identical to the kernels by
tests/test_kernels.py.  Override with the ``REPRO_FUSED_GEMM`` env var
("1"/"0") or the `use_fused_gemms` context manager (tests and CI force the
interpreter path this way).  The decision is made at trace time: re-jit
(or use a fresh function) after toggling.

Accumulation is fp32 (`preferred_element_type`), matching MXU semantics.

The pre-redesign entry points — ``qmatmul``, ``qeinsum_bmm``,
``qdot_attn`` — remain as deprecation shims over :func:`mx_contract`
(bit-identical; see tests/test_qlinear.py) and warn on use.
"""
from __future__ import annotations

import contextlib
import os
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .attnspec import AttnSpec
from .mx import MXWeight, quantize_mx, unpack_mx
from .qconfig import QuantConfig

__all__ = ["mx_contract", "qmatmul", "qeinsum_bmm", "qdot_attn",
           "fused_gemms_enabled", "use_fused_gemms"]

_FUSED_OVERRIDE: Optional[bool] = None


def fused_gemms_enabled() -> bool:
    """Whether mx_contract dispatches to the fused Pallas kernels
    (trace-time)."""
    if _FUSED_OVERRIDE is not None:
        return _FUSED_OVERRIDE
    env = os.environ.get("REPRO_FUSED_GEMM", "auto").lower()
    if env in ("1", "on", "true"):
        return True
    if env in ("0", "off", "false"):
        return False
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def use_fused_gemms(enable: bool):
    """Force fused-kernel dispatch on/off (interpret mode when off-TPU)."""
    global _FUSED_OVERRIDE
    prev = _FUSED_OVERRIDE
    _FUSED_OVERRIDE = bool(enable)
    try:
        yield
    finally:
        _FUSED_OVERRIDE = prev


def _kernels():
    # Imported lazily: repro.kernels itself imports repro.core submodules.
    from repro import kernels
    return kernels


def _fused(cfg: QuantConfig, *fmts) -> bool:
    return (fused_gemms_enabled() and cfg.scale_mode == "floor"
            and any(f is not None for f in fmts))


def _attn_fmt(cfg: QuantConfig):
    return cfg.a_fwd if cfg.attn else None


def _attn_fused(cfg: QuantConfig) -> bool:
    # Unlike the GEMMs, bf16 attention also benefits from the fused kernel
    # (online softmax + tile skipping), so no quantized operand is required;
    # non-floor scale modes still go through the emulation oracle.
    return fused_gemms_enabled() and (
        _attn_fmt(cfg) is None or cfg.scale_mode == "floor")


def _mm(a: jax.Array, b: jax.Array, out_dtype) -> jax.Array:
    return jnp.matmul(a, b,
                      preferred_element_type=jnp.float32).astype(out_dtype)


# ---------------------------------------------------------------------------
# "dense": the projection GEMM custom VJP
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dense(x: jax.Array, w: jax.Array, cfg: QuantConfig) -> jax.Array:
    y, _ = _dense_fwd(x, w, cfg)
    return y


def _dense_fwd(x, w, cfg: QuantConfig):
    """Forward of the dense GEMM, by what ``w`` is.

    ``w`` an array (training): the fused kernel reads the bf16 weight and
    quantizes both operands on load; the emulation quantizes both with
    ``quantize_mx``.  ``w`` an :class:`MXWeight` (serving, packed once
    from Q[w_fwd](W)): the fused kernel reads its codes and exponents and
    dequantizes on load; the emulation multiplies Q[a_fwd](x) by
    ``unpack_mx(w)``, which is Q[w_fwd](W) bit for bit, and never
    re-quantizes it (MX re-quantization is not idempotent at block maxima
    that round up)."""
    if isinstance(w, MXWeight):
        if _fused(cfg, cfg.a_fwd, w.fmt):
            y = _kernels().mx_matmul(x, w, cfg.a_fwd, None,
                                     block=cfg.block).astype(x.dtype)
        else:
            xq = quantize_mx(x, cfg.a_fwd, axis=-1, block=cfg.block,
                             scale_mode=cfg.scale_mode)
            y = _mm(xq, unpack_mx(w, x.dtype), x.dtype)
        return y, (x, w)
    if _fused(cfg, cfg.a_fwd, cfg.w_fwd):
        y = _kernels().mx_matmul(x, w, cfg.a_fwd, cfg.w_fwd,
                                 block=cfg.block).astype(x.dtype)
    else:
        xq = quantize_mx(x, cfg.a_fwd, axis=-1, block=cfg.block,
                         scale_mode=cfg.scale_mode)
        wq = quantize_mx(w, cfg.w_fwd, axis=0, block=cfg.block,
                         scale_mode=cfg.scale_mode)
        y = _mm(xq, wq, x.dtype)
    return y, (x, w)


def _dense_bwd(cfg: QuantConfig, res, dy):
    x, w = res
    kdim, ndim = w.shape
    dyf = dy.reshape(-1, ndim)
    xf = x.reshape(-1, kdim)
    if cfg.quantize_bwd:
        # dgrad: contraction (and MX blocks) over N.
        if _fused(cfg, cfg.g_bwd, cfg.w_bwd):
            dx = _kernels().mx_matmul_dgrad(dy, w, cfg.g_bwd, cfg.w_bwd,
                                            block=cfg.block).astype(x.dtype)
        else:
            dyq = quantize_mx(dy, cfg.g_bwd, axis=-1, block=cfg.block,
                              scale_mode=cfg.scale_mode)
            wq = quantize_mx(w, cfg.w_bwd, axis=1, block=cfg.block,
                             scale_mode=cfg.scale_mode)
            dx = _mm(dyq, wq.T, x.dtype)
        # wgrad: contraction (and MX blocks) over tokens.
        if _fused(cfg, cfg.a_bwd, cfg.g_bwd):
            dw = _kernels().mx_matmul_wgrad(xf, dyf, cfg.a_bwd, cfg.g_bwd,
                                            block=cfg.block).astype(w.dtype)
        else:
            xq = quantize_mx(xf, cfg.a_bwd, axis=0, block=cfg.block,
                             scale_mode=cfg.scale_mode)
            dyq2 = quantize_mx(dyf, cfg.g_bwd, axis=0, block=cfg.block,
                               scale_mode=cfg.scale_mode)
            dw = _mm(xq.T, dyq2, w.dtype)
    else:
        dx = _mm(dy, w.T, x.dtype)
        dw = _mm(xf.T, dyf, w.dtype)
    return dx, dw


_dense.defvjp(_dense_fwd, _dense_bwd)


# ---------------------------------------------------------------------------
# "flash_attn": fused attention custom VJP (QK^T + online softmax + PV)
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q: jax.Array, k: jax.Array, v: jax.Array, cfg: QuantConfig,
           spec: AttnSpec) -> jax.Array:
    out, _ = _flash_fwd(q, k, v, cfg, spec)
    return out


def _flash_fwd(q, k, v, cfg: QuantConfig, spec: AttnSpec):
    fmt = _attn_fmt(cfg)
    if _attn_fused(cfg):
        out, lse = _kernels().mx_flash_attention(
            q, k, v, fmt, spec, block=cfg.block, scale_mode=cfg.scale_mode)
    else:
        out, lse = _kernels().mx_flash_attention_ref(
            q, k, v, fmt, spec, block=cfg.block, scale_mode=cfg.scale_mode)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg: QuantConfig, spec: AttnSpec, res, dout):
    q, k, v, out, lse = res
    fmt = _attn_fmt(cfg)
    if _attn_fused(cfg):
        return _kernels().mx_flash_attention_bwd(
            q, k, v, dout, out, lse, fmt, spec, block=cfg.block,
            scale_mode=cfg.scale_mode)
    return _kernels().mx_flash_attention_bwd_ref(
        q, k, v, dout, out, lse, fmt, spec, block=cfg.block,
        scale_mode=cfg.scale_mode)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# mx_contract: the unified dispatcher
# ---------------------------------------------------------------------------
_CONTRACT_KINDS = {}


def _register(kind: str):
    def deco(fn):
        _CONTRACT_KINDS[kind] = fn
        return fn
    return deco


@_register("dense")
def _kind_dense(lhs, rhs, cfg, *, spec, valid, pages):
    if isinstance(rhs, MXWeight):       # frozen weights: no VJP to carry
        return _dense_fwd(lhs, rhs, cfg)[0]
    return _dense(lhs, rhs, cfg)


@_register("bmm")
def _kind_bmm(lhs, rhs, cfg, *, spec, valid, pages):
    assert rhs.ndim == 3 and lhs.ndim >= 3
    lead = lhs.shape[:-3]
    xf = lhs.reshape((-1,) + lhs.shape[-3:]) if lead else lhs[None]
    out = jax.vmap(
        jax.vmap(_dense, in_axes=(0, 0, None)), in_axes=(0, None, None)
    )(xf, rhs, cfg)
    return out.reshape(lead + out.shape[1:]) if lead else out[0]


def _kind_attn_bmm(lhs, rhs, cfg, *, spec, valid, pages):
    if not cfg.attn:
        return _mm(lhs, rhs, lhs.dtype)
    aq = quantize_mx(lhs, cfg.a_fwd, axis=-1, block=cfg.block,
                     scale_mode=cfg.scale_mode)
    bq = quantize_mx(rhs, cfg.a_fwd, axis=-2, block=cfg.block,
                     scale_mode=cfg.scale_mode)
    return _mm(aq, bq, lhs.dtype)


_register("attn_qk")(_kind_attn_bmm)
_register("attn_pv")(_kind_attn_bmm)


@_register("flash_attn")
def _kind_flash(lhs, rhs, cfg, *, spec, valid, pages):
    if spec is None:
        raise ValueError("kind='flash_attn' requires spec=AttnSpec(...)")
    k, v = rhs
    return _flash(lhs, k, v, cfg, spec)


@_register("attn_decode")
def _kind_decode(lhs, rhs, cfg, *, spec, valid, pages):
    if valid is None:
        raise ValueError("kind='attn_decode' requires valid=(BH, S) mask")
    k, v = rhs
    fmt = _attn_fmt(cfg)
    if _attn_fused(cfg):
        return _kernels().mx_attention_decode(
            lhs, k, v, valid, fmt, block=cfg.block,
            scale_mode=cfg.scale_mode)
    return _kernels().mx_attention_decode_ref(
        lhs, k, v, valid, fmt, block=cfg.block, scale_mode=cfg.scale_mode)


@_register("attn_decode_paged")
def _kind_decode_paged(lhs, rhs, cfg, *, spec, valid, pages):
    if valid is None or pages is None:
        raise ValueError("kind='attn_decode_paged' requires valid=(B, P*ps) "
                         "mask and pages=(B, P) page table")
    k_pool, v_pool = rhs
    fmt = _attn_fmt(cfg)
    if _attn_fused(cfg):
        return _kernels().mx_attention_decode_paged(
            lhs, k_pool, v_pool, pages, valid, fmt, block=cfg.block,
            scale_mode=cfg.scale_mode)
    return _kernels().mx_attention_decode_paged_ref(
        lhs, k_pool, v_pool, pages, valid, fmt, block=cfg.block,
        scale_mode=cfg.scale_mode)


def mx_contract(lhs, rhs, cfg: QuantConfig, *, kind: str = "dense",
                spec: Optional[AttnSpec] = None,
                valid: Optional[jax.Array] = None,
                pages: Optional[jax.Array] = None) -> jax.Array:
    """Quantized contraction, dispatched on ``kind`` (see module docstring).

    ``rhs`` is a single array for the GEMM/BMM kinds and a ``(k, v)`` pair
    for the attention kinds; ``spec`` parameterizes flash-attention masking
    and tiling; ``valid`` is the decode-cache validity mask; ``pages`` is
    the (B, P) page table for the paged decode kind."""
    try:
        impl = _CONTRACT_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown mx_contract kind {kind!r}; "
            f"expected one of {sorted(_CONTRACT_KINDS)}") from None
    return impl(lhs, rhs, cfg, spec=spec, valid=valid, pages=pages)


# ---------------------------------------------------------------------------
# Deprecation shims (pre-redesign entry points)
# ---------------------------------------------------------------------------
def _deprecated(old: str, new: str):
    warnings.warn(f"{old} is deprecated; use {new}",
                  DeprecationWarning, stacklevel=3)


def qmatmul(x: jax.Array, w: jax.Array, cfg: QuantConfig) -> jax.Array:
    """Deprecated: use ``mx_contract(x, w, cfg, kind="dense")``."""
    _deprecated("qmatmul(x, w, cfg)", 'mx_contract(x, w, cfg, kind="dense")')
    return mx_contract(x, w, cfg, kind="dense")


def qeinsum_bmm(x: jax.Array, w: jax.Array, cfg: QuantConfig) -> jax.Array:
    """Deprecated: use ``mx_contract(x, w, cfg, kind="bmm")``."""
    _deprecated("qeinsum_bmm(x, w, cfg)",
                'mx_contract(x, w, cfg, kind="bmm")')
    return mx_contract(x, w, cfg, kind="bmm")


def qdot_attn(a: jax.Array, b: jax.Array, cfg: QuantConfig) -> jax.Array:
    """Deprecated: use ``mx_contract(a, b, cfg, kind="attn_qk"/"attn_pv")``."""
    _deprecated("qdot_attn(a, b, cfg)",
                'mx_contract(a, b, cfg, kind="attn_pv")')
    return mx_contract(a, b, cfg, kind="attn_pv")
