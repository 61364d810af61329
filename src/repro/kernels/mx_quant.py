"""Pallas TPU kernel: fused MX block-scale quantize-dequantize.

TPU adaptation of the paper's quantization hot-spot (Algorithm 1).  On
Blackwell, MX casting is fused into the tensor-core datapath; the TPU-native
equivalent is a VMEM-tiled elementwise pipeline: stream (TILE_M, K) tiles
HBM→VMEM, compute per-32-lane shared exponents via exponent-field
extraction in VREGs (no transcendentals), cast onto the element grid with
round-half-to-even, and write the dequantized tile back — one HBM round
trip for the whole quantize-dequantize, instead of the max / log2 / div /
round / mul chain each touching HBM.

Scale math uses bit manipulation exclusively (exp2 of an integer is an
exponent-field shift), so the kernel is MXU-free and VPU-bound.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import (SCALE_EMAX, SCALE_EMIN, ElementFormat,
                                exp2_int, floor_log2)
from repro.core.mx import MX_BLOCK

__all__ = ["mx_quantize_pallas"]

# Elements per (tile_m, K) block.  A block costs ~20 bytes per element of
# scoped VMEM (double-buffered bf16 in and out plus the fp32 temporaries);
# 2^19 of them stay under the TPU v5e's 16 MiB default scoped limit, so
# wide rows trade tile height for width instead of failing to compile.
_TILE_ELEMS = 1 << 19


def _block_absmax(x: jax.Array, block: int) -> jax.Array:
    """Max of ``|x|`` over each aligned run of ``block`` lanes of a 2D
    tile, broadcast back to every lane of the run (same shape as x).

    Mosaic refuses the reshape that splits a tile's lane dim into
    (n_blocks, block), so the reduction is an XOR butterfly instead: at
    stride s every lane takes the max with its partner ``lane ^ s`` (two
    ``pltpu.roll``s and a select), and after log2(block) strides each lane
    holds its whole block's max.  It runs on the fp32 bit patterns of
    ``|x|`` as int32 — for non-negative floats integer order is float order,
    with NaN patterns above inf, so the result is bitwise ``jnp.max``
    (NaN-propagating) of the block.
    """
    n = x.shape[-1]
    if block & (block - 1) or n % block:
        raise ValueError(f"block={block} must be a power of two dividing "
                         f"the tile width {n}")
    bits = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < block:
        nxt = pltpu.roll(bits, n - s, 1)       # lane i sees lane i + s
        prv = pltpu.roll(bits, s, 1)           # lane i sees lane i - s
        bits = jnp.maximum(bits, jnp.where((lane & s) == 0, nxt, prv))
        s *= 2
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _quantize_block_tile(x: jax.Array, fmt: ElementFormat, block: int
                         ) -> jax.Array:
    """Quantize a (TM, K) fp32 tile with blocks of ``block`` along axis -1.

    Same exact arithmetic as the numerics core (shared exp2_int /
    floor_log2 bit manipulation — no transcendentals), restructured for a
    VMEM-resident tile: after the block max every step is elementwise, so
    the tile keeps its (TM, K) layout.
    """
    m = _block_absmax(x, block)
    e = floor_log2(jnp.where(m > 0, m, 1.0)) - fmt.e_max
    e = jnp.clip(e, SCALE_EMIN + 1, SCALE_EMAX)
    e = jnp.where(m > 0, e, SCALE_EMIN + 1)
    scale = exp2_int(e)
    r = x / scale  # exact: scale is a power of two
    # Element cast: round-half-even within the exponent bin, clamp overflow.
    mag = jnp.abs(r)
    ee = floor_log2(jnp.where(mag > 0, mag, 1.0))
    ee = jnp.maximum(ee, fmt.min_normal_exp)
    quantum = exp2_int(ee - fmt.mbits)
    q = jnp.round(r / quantum) * quantum
    q = jnp.clip(q, -fmt.max_normal, fmt.max_normal)
    q = jnp.where(mag > 0, q, 0.0)
    q = jnp.where(jnp.isfinite(r), q, r)
    return q * scale


def _mx_quant_kernel(x_ref, o_ref, *, fmt: ElementFormat, block: int):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = _quantize_block_tile(x, fmt, block).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block", "tile_m", "interpret"))
def mx_quantize_pallas(x: jax.Array, fmt: ElementFormat,
                       block: int = MX_BLOCK, tile_m: int = 256,
                       interpret: bool = False) -> jax.Array:
    """Quantize-dequantize a 2D array (M, K) with blocks along axis -1.

    K must be a multiple of ``block``; M is padded up to ``tile_m``
    internally, and ``tile_m`` shrinks (to a multiple of 8) where a block
    of whole rows would exceed ``_TILE_ELEMS``.  Higher-rank /
    arbitrary-axis handling lives in :mod:`repro.kernels.ops`.
    """
    m, k = x.shape
    if k % block:
        raise ValueError(f"K={k} not a multiple of block={block}")
    tile_m = min(tile_m, max(8, _TILE_ELEMS // k // 8 * 8), max(1, m))
    pad_m = (-m) % tile_m
    xp = jnp.pad(x, ((0, pad_m), (0, 0))) if pad_m else x
    grid = ((m + pad_m) // tile_m,)
    out = pl.pallas_call(
        functools.partial(_mx_quant_kernel, fmt=fmt, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_m, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_m, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
    )(xp)
    return out[:m] if pad_m else out
