"""Pallas TPU kernel: MX-quantized GEMM, quantize- or dequantize-on-load.

The TPU-native realization of MX GEMM: rather than materializing quantized
copies of A and B in HBM (two extra round trips), each (TM,TK)/(TK,TN) tile
is quantized *after* the HBM→VMEM copy and immediately fed to the MXU in
bf16-dequantized form with fp32 accumulation.  MX blocks (32 lanes) run
along the contraction axis for both operands, so block boundaries align
with K-tiles whenever 32 | TK and the shared scales factor out of every
partial dot product — the fused result is bit-identical to quantizing the
whole operands up front (ref.py oracle).

Tiles default to MXU-aligned (multiples of 128); the fp32 accumulator lives
in a VMEM scratch buffer across the K grid dimension.

This is the *forward* GEMM of the quantized training step (blocks along
K); the dgrad (blocks along N) and wgrad (blocks along T) siblings live in
mx_matmul_bwd.py:

      forward  : y  = Q[a_fwd](x) @ Q[w_fwd](W)       blocks along K
      dgrad    : dx = Q[g_bwd](dy) @ Q[w_bwd](W)^T    blocks along N
      wgrad    : dW = Q[a_bwd](x)^T @ Q[g_bwd](dy)    blocks along T

Two forward paths share the entry :func:`mx_matmul_pallas`, chosen by the
type of ``b``:

  * ``b`` a plain array (training): quantize-on-load.  The kernel reads
    bf16 weight tiles (2 B per weight) and re-quantizes them in VMEM on
    every call, with fp32 operands to the MXU.
  * ``b`` an :class:`~repro.core.mx.MXWeight` (serving, weights frozen):
    dequantize-on-load.  The kernel reads one byte of element code per
    weight plus one E8M0 byte per 32 (1.03 B per weight), rebuilds each
    ``block`` rows of a tile from the codes' bit fields and the block's
    exponent, and feeds bf16 operands to the MXU with fp32 accumulation.
    Every Q(W) and Q(x) value is exact in bf16 and every product exact in
    fp32, so this is the same arithmetic as the fp32 dot, in fewer MXU
    passes.  Tiles follow the call's shapes: one M tile up to 256 rows (a
    prefill chunk dequantizes each weight tile once) and K x N tiles of
    up to ``_PACKED_TILE_ELEMS`` weights, so a decode forward takes a few
    thousand grid steps rather than tens of thousands.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import ElementFormat
from repro.core.mx import MX_BLOCK, MXWeight, decode_codes
from .mx_quant import _quantize_block_tile

__all__ = ["mx_matmul_pallas"]


def _mx_mm_kernel(a_ref, b_ref, o_ref, acc_ref, *,
                  fmt_a: Optional[ElementFormat],
                  fmt_b: Optional[ElementFormat], block: int, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    if fmt_a is not None:
        a = _quantize_block_tile(a, fmt_a, block)          # blocks along K
    if fmt_b is not None:
        bt = _quantize_block_tile(b.T, fmt_b, block)       # blocks along K
        b = bt.T
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# Weights per packed (tile_k, tile_n) tile.  With a 256-row M tile the
# scoped VMEM holds, double-buffered, the activation tile (2 B per
# element) and the code tile (1 B), plus the dequantized bf16 tile, the
# fp32 accumulator and the output: about 10.5 MiB at 1.5 M weights, under
# the TPU v5e's 16 MiB default scoped limit.
_PACKED_TILE_ELEMS = 3 << 19
# Activation elements per tile where the kernel also quantizes the
# activations: the quantizer's fp32 temporaries take about 12 B each.
_QUANT_A_TILE_ELEMS = 1 << 19
# Packed M tile: up to this many rows run against one dequantized tile.
_PACKED_TILE_M = 256
# Blocks dequantized per loop trip: one fp32 sublane tile of scales.
_GROUP = 8


def _packed_tiles(m: int, k: int, n: int, block: int, quant_a: bool):
    """(tile_m, tile_n, tile_k) of the packed path for an (M,K) @ (K,N)
    call.  The weight is padded only where no tile divides it: tile_n
    divides N (or N is narrower than a lane tile) unless N is not a
    multiple of 128; tile_k is K, or the largest multiple of 8 * block
    (whole fp32 sublane tiles of block scales) that divides K and fits."""
    tm = min(-(-m // 16) * 16, _PACKED_TILE_M)     # whole bf16 sublane tiles
    kmax = _QUANT_A_TILE_ELEMS // tm if quant_a else k
    lanes = -(-n // 128) * 128
    tn = n if n < 128 else next(c for c in (512, 256, 128) if lanes % c == 0)
    if k * tn <= _PACKED_TILE_ELEMS and k <= kmax:
        return tm, tn, k
    step = _GROUP * block
    fit = max(step, min(_PACKED_TILE_ELEMS // tn, kmax) // step * step)
    tks = [c for c in range(step, fit + 1, step) if k % c == 0]
    return tm, tn, max(tks) if tks else fit


def _mx_mm_packed_kernel(a_ref, c_ref, e_ref, o_ref, acc_ref, s_ref, w_ref,
                         *, fmt_a: Optional[ElementFormat],
                         fmt_b: ElementFormat, block: int, k_steps: int,
                         group: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The tile's block scales: an E8M0 byte is the fp32 exponent field.
    s_ref[...] = jax.lax.bitcast_convert_type(
        e_ref[...].astype(jnp.int32) << 23, jnp.float32)
    rows = group * block

    def dequant(g, carry):
        scales = s_ref[pl.ds(pl.multiple_of(g * group, group), group), :]
        for j in range(group):
            r = pl.multiple_of(g * rows + j * block, block)
            q = decode_codes(c_ref[pl.ds(r, block), :].astype(jnp.int32),
                             fmt_b)
            w_ref[pl.ds(r, block), :] = (
                q * scales[j:j + 1, :]).astype(w_ref.dtype)
        return carry

    jax.lax.fori_loop(0, s_ref.shape[0] // group, dequant, 0)
    a = a_ref[...]
    if fmt_a is not None:
        a = _quantize_block_tile(a.astype(jnp.float32), fmt_a, block)
    acc_ref[...] += jnp.dot(a.astype(w_ref.dtype), w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mx_matmul_packed(a: jax.Array, b: MXWeight,
                      fmt_a: Optional[ElementFormat], tiles,
                      interpret: bool) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and b.ndim == 2, (a.shape, b.shape)
    block = b.block
    tile_m, tile_n, tile_k = tiles or _packed_tiles(m, k, n, block,
                                                    fmt_a is not None)
    if tile_k % block:
        raise ValueError(f"tile_k={tile_k} not a multiple of block={block}")
    # Zero padding: zero codes with zero scales contribute nothing.
    pm, pn, pk = (-m) % tile_m, (-n) % tile_n, (-k) % tile_k
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    codes, exps = b.codes, b.exps
    if pk or pn:
        codes = jnp.pad(codes, ((0, pk), (0, pn)))
        exps = jnp.pad(exps, ((0, pk // block), (0, pn)))
    nb = tile_k // block
    group = _GROUP if nb % _GROUP == 0 else nb
    # MXU operands: bf16 on the chip, where Q(W), Q(x) and bf16 x are
    # exact and every product is exact in fp32.  The interpreter keeps
    # fp32: XLA:CPU accumulates a bf16 dot in another order than an fp32
    # one, and the fp32 dot is the one the quantize-on-load path runs.
    exact_bf16 = fmt_a is not None or a.dtype == jnp.bfloat16
    wdt = jnp.bfloat16 if exact_bf16 and not interpret else jnp.float32
    gm, gn, gk = (m + pm) // tile_m, (n + pn) // tile_n, (k + pk) // tile_k
    out = pl.pallas_call(
        functools.partial(_mx_mm_packed_kernel, fmt_a=fmt_a, fmt_b=b.fmt,
                          block=block, k_steps=gk, group=group),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tile_k, tile_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((nb, tile_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), a.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32),
                        pltpu.VMEM((nb, tile_n), jnp.float32),
                        pltpu.VMEM((tile_k, tile_n), wdt)],
        interpret=interpret,
    )(ap, codes, exps)
    return out[:m, :n] if (pm or pn) else out


@functools.partial(jax.jit, static_argnames=(
    "fmt_a", "fmt_b", "block", "tile_m", "tile_n", "tile_k", "interpret"))
def mx_matmul_pallas(a: jax.Array, b, fmt_a: Optional[ElementFormat],
                     fmt_b: Optional[ElementFormat],
                     block: int = MX_BLOCK, tile_m: Optional[int] = None,
                     tile_n: Optional[int] = None,
                     tile_k: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """``a (M,K) @ b (K,N)`` with MX quantization of both operands.

    ``b`` a plain array: both operands are quantized on load; tiles
    default to 128/128/256.  K must be a multiple of ``block``; all dims
    are padded to tile multiples (zero padding adds all-zero MX blocks,
    which quantize to zero and contribute nothing to the accumulation).

    ``b`` an :class:`MXWeight` (``fmt_b`` None or ``b.fmt``): its
    codes and exponents are dequantized on load; tiles default to the
    shapes' own (:func:`_packed_tiles`), or are given all three.
    """
    if isinstance(b, MXWeight):
        if fmt_b not in (None, b.fmt) or b.block != block:
            raise ValueError(f"fmt_b={fmt_b}, block={block}, but b is "
                             f"packed as {b.fmt} in blocks of {b.block}")
        given = (tile_m, tile_n, tile_k)
        return _mx_matmul_packed(
            a, b, fmt_a, None if None in given else given, interpret)
    tile_m = 128 if tile_m is None else tile_m
    tile_n = 128 if tile_n is None else tile_n
    tile_k = 256 if tile_k is None else tile_k
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if k % block:
        raise ValueError(f"K={k} not a multiple of block={block}")
    tile_m, tile_n = min(tile_m, m), min(tile_n, n)
    tile_k = min(tile_k, k)
    if tile_k % block:
        raise ValueError(f"tile_k={tile_k} not a multiple of block={block}")
    pm, pn, pk = (-m) % tile_m, (-n) % tile_n, (-k) % tile_k
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if (pk or pn) else b
    gm, gn, gk = (m + pm) // tile_m, (n + pn) // tile_n, (k + pk) // tile_k
    out = pl.pallas_call(
        functools.partial(_mx_mm_kernel, fmt_a=fmt_a, fmt_b=fmt_b,
                          block=block, k_steps=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tile_k, tile_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), a.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
