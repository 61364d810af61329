"""MX block-scaled quantization (Algorithm 1 of the paper).

A block of k=32 consecutive values along a chosen axis shares a single
power-of-two scale ``X = 2^(floor(log2 max|V|) - e_max_elem)`` (stored as
E8M0); elements are cast to the low-precision element format after dividing
by ``X``.  This module implements the *emulated* ("fake-quant") form: arrays
stay in their container dtype but carry exactly representable MX values —
the same methodology as the paper's MX PyTorch emulation library.

Scale modes:
  * "floor"    — the OCP / Algorithm-1 rule (paper baseline).
  * "bump"     — +1 on the shared exponent for blocks that would clamp
                 (the paper's Fig. 7 "bumping exponent" intervention).
  * "adaptive" — choose between floor-exp and floor-exp+1 per block by
                 least squared error (the paper's "scale that adapts" future
                 direction, §6.1; a beyond-paper feature we evaluate).

:class:`MXWeight` is the packed ("at rest") form of a quantized weight:
element codes of one byte each plus one E8M0 shared exponent per block,
as MX hardware stores it.  :func:`pack_mx` builds it once, for weights that
never change (serving); :func:`unpack_mx` gives back exactly what
``quantize_mx(w, fmt, axis=-2)`` gives.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .formats import (SCALE_EMAX, SCALE_EMIN, ElementFormat, exp2_int,
                      floor_log2, quantize_elem)

__all__ = [
    "quantize_mx", "mx_stats", "block_reshape", "block_unreshape",
    "shared_exponent", "MX_BLOCK", "MXWeight", "pack_mx", "unpack_mx",
    "decode_codes",
]

MX_BLOCK = 32  # hardware block size (paper trains with k=32 throughout)


def block_reshape(x: jax.Array, axis: int, block: int
                  ) -> Tuple[jax.Array, int]:
    """Move ``axis`` last and fold it into (..., n_blocks, block).

    Returns the blocked array and the original (unpadded) axis length.
    Zero-pads to a block multiple; padded lanes live in their own tail
    positions and only share a block with real values when the axis is not
    a block multiple — zeros never raise a block max, so real values are
    unaffected.
    """
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(x.shape[:-1] + ((n + pad) // block, block))
    return xb, n


def block_unreshape(xb: jax.Array, axis: int, n: int) -> jax.Array:
    """Inverse of :func:`block_reshape`."""
    x = xb.reshape(xb.shape[:-2] + (xb.shape[-2] * xb.shape[-1],))
    x = x[..., :n]
    return jnp.moveaxis(x, -1, axis)


def shared_exponent(xb: jax.Array, fmt: ElementFormat,
                    scale_mode: str = "floor") -> jax.Array:
    """Per-block shared exponent (Algorithm 1, line 3), int32 (..., nb, 1)."""
    m = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    e = floor_log2(jnp.where(m > 0, m, 1.0)) - fmt.e_max
    if scale_mode == "bump":
        # Bump blocks in which any value would overflow past max_normal after
        # division by the floor scale (paper Fig. 7 intervention).
        x_over = jnp.abs(xb) / exp2_int(e)
        overflow = jnp.any(x_over > fmt.max_normal, axis=-1, keepdims=True)
        e = e + overflow.astype(jnp.int32)
    elif scale_mode == "adaptive":
        err0 = _block_sq_err(xb, e, fmt)
        err1 = _block_sq_err(xb, e + 1, fmt)
        e = jnp.where(err1 < err0, e + 1, e)
    elif scale_mode != "floor":
        raise ValueError(f"unknown scale_mode {scale_mode!r}")
    # E8M0 range is [-127, 127]; we additionally keep scales in the fp32
    # normal range so exponent-field exp2 stays exact (blocks whose max is
    # below ~2^(-126+e_max) are indistinguishable from zero anyway).
    e = jnp.clip(e, SCALE_EMIN + 1, SCALE_EMAX)
    # All-zero block: any scale works; use the minimum.
    e = jnp.where(m > 0, e, SCALE_EMIN + 1)
    return e


def _block_sq_err(xb: jax.Array, e: jax.Array, fmt: ElementFormat) -> jax.Array:
    scale = exp2_int(e)
    y = quantize_elem(xb / scale, fmt) * scale
    return jnp.sum(jnp.square(y - xb), axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("fmt", "axis", "block", "scale_mode"))
def quantize_mx(x: jax.Array, fmt: Optional[ElementFormat], axis: int = -1,
                block: int = MX_BLOCK, scale_mode: str = "floor") -> jax.Array:
    """Quantize-dequantize ``x`` to the MX grid along ``axis``.

    ``fmt=None`` (bf16 sentinel) returns ``x`` unchanged.  The result has the
    same dtype/shape as ``x`` and carries only values exactly representable
    as ``element x 2^shared_exp`` (elements on ``fmt``'s grid).

    Straight-through gradient: like the MX emulation library, autodiff
    treats the quantizer as identity (``round`` has zero derivative a.e.,
    which would otherwise silently kill gradients through quantized
    layer-norm affine and attention paths).
    """
    if fmt is None:
        return x
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    xb, n = block_reshape(xf, axis, block)
    e = shared_exponent(xb, fmt, scale_mode)
    scale = exp2_int(e)
    q = quantize_elem(xb / scale, fmt)
    yb = q * scale
    y = block_unreshape(yb, axis, n)
    # Straight-through estimator, assembled in fp32: xf + (y - xf) == y
    # exactly, so the forward value sits exactly on the MX grid even for
    # bf16 containers (computing the STE in bf16 double-rounds, drifting
    # 1 ulp off-grid and off the fused kernels' values); every MX element
    # times a power-of-two scale is bf16-representable, so the final cast
    # is exact too.
    return (xf + jax.lax.stop_gradient(y - xf)).astype(orig_dtype)


@partial(jax.jit, static_argnames=("fmt", "axis", "block", "scale_mode"))
def mx_stats(x: jax.Array, fmt: ElementFormat, axis: int = -1,
             block: int = MX_BLOCK, scale_mode: str = "floor") -> dict:
    """Clamping diagnostics for the paper's Fig. 5 / Eq. 10 analysis.

    Returns (scalars):
      overflow_frac   — fraction of values with |v/X| > max_normal (clamped).
      last_bin_frac   — fraction of values that quantize to ±max_normal
                        ("end up in the last quantization bin").
      tight_block_frac— fraction of blocks in which *every* value lands in
                        the last bin (heterogeneity fully lost — the paper's
                        layernorm-affine failure mode).
      rel_err         — mean |y - x| / (|x| + eps) quantization error.
    """
    xf = x.astype(jnp.float32)
    xb, n = block_reshape(xf, axis, block)
    # Mask out padded lanes so they do not dilute fractions.
    mask = (jnp.arange(xb.shape[-1] * xb.shape[-2]).reshape(xb.shape[-2:])
            < n)
    mask = jnp.broadcast_to(mask, xb.shape)
    e = shared_exponent(xb, fmt, scale_mode)
    scale = exp2_int(e)
    r = xb / scale
    q = quantize_elem(r, fmt)
    total = jnp.maximum(jnp.sum(mask), 1)
    overflow = jnp.sum((jnp.abs(r) > fmt.max_normal) & mask) / total
    last_bin = (jnp.abs(q) >= fmt.max_normal) & mask
    last_bin_frac = jnp.sum(last_bin) / total
    tight = jnp.all(last_bin | ~mask, axis=-1) & jnp.any(mask, axis=-1)
    tight_block_frac = jnp.mean(tight.astype(jnp.float32))
    y = q * scale
    rel_err = jnp.sum(jnp.abs(y - xb) / (jnp.abs(xb) + 1e-12) * mask) / total
    return {
        "overflow_frac": overflow,
        "last_bin_frac": last_bin_frac,
        "tight_block_frac": tight_block_frac,
        "rel_err": rel_err,
    }


# ---------------------------------------------------------------------------
# Packed weights: element codes + E8M0 shared exponents
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MXWeight:
    """An MX-quantized weight ``(..., K, N)`` stored packed, blocks along K.

    ``codes``: uint8 ``(..., K, N)``, one element code each (sign, then
    ``fmt.ebits`` exponent and ``fmt.mbits`` mantissa bits, right-aligned).
    ``exps``: uint8 ``(..., K // block, N)``, each block's E8M0 shared
    exponent (biased by 127).  ``fmt`` is static pytree metadata, so
    ``lax.scan`` over a stacked leading axis slices both arrays together.
    """

    codes: jax.Array
    exps: jax.Array
    fmt: ElementFormat = dataclasses.field(metadata=dict(static=True))

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.codes.shape

    @property
    def ndim(self) -> int:
        return self.codes.ndim

    @property
    def block(self) -> int:
        return self.codes.shape[-2] // self.exps.shape[-2]

    @property
    def nbytes(self) -> int:
        return self.codes.nbytes + self.exps.nbytes


def _encode_elem(q: jax.Array, fmt: ElementFormat) -> jax.Array:
    """fp32 values on ``fmt``'s grid -> element codes (int32).  Zeros of
    either sign encode as +0, the zero ``quantize_mx`` returns."""
    bits = jax.lax.bitcast_convert_type(q, jnp.uint32)
    sign = (bits >> 31).astype(jnp.int32)
    e = ((bits >> 23) & 0xFF).astype(jnp.int32) - 127 + fmt.bias
    m = ((bits & 0x7FFFFF) >> (23 - fmt.mbits)).astype(jnp.int32)
    normal = (e << fmt.mbits) | m
    # Subnormal codes (exponent field 0) count quanta of the subnormal step.
    sub = jnp.round(jnp.abs(q) / fmt.min_subnormal).astype(jnp.int32)
    mag = jnp.where(e >= 1, normal, sub)
    return jnp.where(mag > 0, (sign << (fmt.ebits + fmt.mbits)) | mag, 0)


def decode_codes(c: jax.Array, fmt: ElementFormat) -> jax.Array:
    """Element codes (int32) -> their fp32 values, with integer and float
    ops alone: normal codes move their exponent and mantissa fields into
    an fp32 bit pattern; subnormal codes (exponent field 0), which an fp32
    pattern with the format's bias cannot express without fp32 subnormals,
    scale their mantissa by the subnormal step instead.  The Pallas GEMM
    runs this same function on its weight tiles."""
    width = fmt.ebits + fmt.mbits
    mag = c & ((1 << width) - 1)
    sign = (c >> width) << 31
    normal = jax.lax.bitcast_convert_type(
        (mag << (23 - fmt.mbits)) + ((127 - fmt.bias) << 23), jnp.float32)
    sub = mag.astype(jnp.float32) * fmt.min_subnormal
    val = jnp.where(mag < (1 << fmt.mbits), sub, normal)
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(val, jnp.int32) | sign, jnp.float32)


def _pack_2d(w: jax.Array, fmt: ElementFormat, block: int, scale_mode: str,
             dtype) -> Tuple[jax.Array, jax.Array]:
    w = w.astype(dtype)
    xb, _ = block_reshape(w.astype(jnp.float32), 0, block)   # (N, nb, blk)
    e = shared_exponent(xb, fmt, scale_mode)
    q = quantize_elem(xb / exp2_int(e), fmt)
    codes = _encode_elem(q, fmt).astype(jnp.uint8)
    codes = codes.reshape(codes.shape[0], -1).T               # (K, N)
    exps = (e[..., 0] + 127).astype(jnp.uint8).T              # (nb, N)
    return codes, exps


@partial(jax.jit, static_argnames=("fmt", "block", "scale_mode", "dtype"))
def pack_mx(w: jax.Array, fmt: ElementFormat, block: int = MX_BLOCK,
            scale_mode: str = "floor", dtype=None) -> MXWeight:
    """Pack ``w (..., K, N)`` into an :class:`MXWeight`, blocks along K.

    What is packed is ``quantize_mx(w.astype(dtype), fmt, axis=-2)``
    (``dtype`` None: ``w.dtype``), so a consumer that casts its weight
    before quantizing, as ``qdense`` casts to the activations' dtype, is
    matched bit for bit.  K must be a multiple of ``block``.  Leading axes
    (a stack of layers) are packed one (K, N) slice at a time, so the
    temporaries never exceed one slice.  ``fmt`` must fit a byte."""
    if fmt.bits > 8:
        raise ValueError(f"{fmt} does not fit one byte per element")
    if w.ndim < 2 or w.shape[-2] % block:
        raise ValueError(f"K of {w.shape} is not a multiple of {block}")
    lead, (k, n) = w.shape[:-2], w.shape[-2:]
    one = partial(_pack_2d, fmt=fmt, block=block, scale_mode=scale_mode,
                  dtype=dtype or w.dtype)
    if not lead:
        codes, exps = one(w)
    else:
        codes, exps = jax.lax.map(one, w.reshape((-1, k, n)))
        codes = codes.reshape(lead + (k, n))
        exps = exps.reshape(lead + (k // block, n))
    return MXWeight(codes, exps, fmt)


@partial(jax.jit, static_argnames=("dtype",))
def unpack_mx(w: MXWeight, dtype=jnp.bfloat16) -> jax.Array:
    """The dequantized weight: bit for bit ``quantize_mx(W, fmt, axis=-2)``
    of the ``W`` that was packed (for the scale mode it was packed with),
    in ``dtype``."""
    q = decode_codes(w.codes.astype(jnp.int32), w.fmt)
    scale = exp2_int(w.exps.astype(jnp.int32) - 127)
    scale = jnp.repeat(scale, w.block, axis=-2)
    return (q * scale).astype(dtype)
