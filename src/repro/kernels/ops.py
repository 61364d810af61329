"""Jit'd dispatch wrappers for the Pallas MX kernels.

Handle arbitrary rank/axis by folding to 2D, pick interpret mode
automatically off-TPU (this container is CPU-only; TPU is the target), and
fall back to the pure-jnp reference for shapes the kernels don't cover
(K not a block multiple).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attnspec import AttnSpec
from repro.core.formats import ElementFormat
from repro.core.mx import MX_BLOCK
from . import ref
from .mx_attention import (attn_tiles, mx_attn_bwd_pallas,
                           mx_attn_decode_paged_pallas,
                           mx_attn_decode_pallas, mx_attn_fwd_pallas)
from .mx_matmul import mx_matmul_pallas
from .mx_matmul_bwd import mx_matmul_dgrad_pallas, mx_matmul_wgrad_pallas
from .mx_quant import mx_quantize_pallas

__all__ = ["mx_quantize", "mx_matmul", "mx_matmul_dgrad", "mx_matmul_wgrad",
           "mx_flash_attention", "mx_flash_attention_bwd",
           "mx_attention_decode", "mx_attention_decode_paged"]


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("fmt", "axis", "block"))
def mx_quantize(x: jax.Array, fmt: Optional[ElementFormat], axis: int = -1,
                block: int = MX_BLOCK) -> jax.Array:
    """Kernel-backed quantize-dequantize along ``axis`` for any rank."""
    if fmt is None:
        return x
    ax = axis % x.ndim
    if x.shape[ax] % block:
        return ref.mx_quantize_ref(x, fmt, axis=ax, block=block)
    xm = jnp.moveaxis(x, ax, -1)
    lead = xm.shape[:-1]
    x2 = xm.reshape(-1, xm.shape[-1])
    y2 = mx_quantize_pallas(x2, fmt, block=block,
                            interpret=_use_interpret())
    return jnp.moveaxis(y2.reshape(lead + (xm.shape[-1],)), -1, ax)


@functools.partial(jax.jit, static_argnames=("fmt_a", "fmt_b", "block"))
def mx_matmul(a: jax.Array, b: jax.Array,
              fmt_a: Optional[ElementFormat],
              fmt_b: Optional[ElementFormat],
              block: int = MX_BLOCK) -> jax.Array:
    """Kernel-backed ``a (..., K) @ b (K, N)`` with MX-quantized operands;
    ``b`` an ``MXWeight`` takes the dequantize-on-load path."""
    if a.shape[-1] % block:
        return ref.mx_matmul_ref(a, b, fmt_a, fmt_b, block=block)
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    y2 = mx_matmul_pallas(a2, b, fmt_a, fmt_b, block=block,
                          interpret=_use_interpret())
    return y2.reshape(lead + (b.shape[-1],))


@functools.partial(jax.jit, static_argnames=("fmt_g", "fmt_w", "block"))
def mx_matmul_dgrad(dy: jax.Array, w: jax.Array,
                    fmt_g: Optional[ElementFormat],
                    fmt_w: Optional[ElementFormat],
                    block: int = MX_BLOCK) -> jax.Array:
    """Kernel-backed dgrad ``dy (..., N) @ w (K, N)^T`` -> (..., K).

    Both operands carry MX blocks along N (the dgrad contraction axis);
    ``w`` stays in its forward (K, N) layout.  Falls back to the jnp oracle
    when N is not a block multiple."""
    if dy.shape[-1] % block:
        return ref.mx_matmul_dgrad_ref(dy, w, fmt_g, fmt_w, block=block)
    lead = dy.shape[:-1]
    dy2 = dy.reshape(-1, dy.shape[-1])
    y2 = mx_matmul_dgrad_pallas(dy2, w, fmt_g, fmt_w, block=block,
                                interpret=_use_interpret())
    return y2.reshape(lead + (w.shape[0],))


def _attn_kernel_ok(fmt: Optional[ElementFormat], scale_mode: str,
                    d: int, tile_k: int, block: int) -> bool:
    """Kernel eligibility: quantized tiles need block-multiple MX axes
    (d for QK^T, the kv tile for PV) and the floor scale rule (the only
    one _quantize_block_tile implements); bf16 attention has no such
    constraint."""
    if scale_mode != "floor":
        return False
    return fmt is None or (d % block == 0 and tile_k % block == 0)


@functools.partial(jax.jit, static_argnames=("fmt", "spec", "block",
                                             "scale_mode"))
def mx_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       fmt: Optional[ElementFormat], spec: AttnSpec,
                       block: int = MX_BLOCK, scale_mode: str = "floor"):
    """Kernel-backed flash-attention forward on the folded layout
    (q (BH,G,Tq,d), k (BH,Tk,d), v (BH,Tk,dv)) -> (out, lse).

    Falls back to the jnp oracle for non-floor scale modes or MX axes that
    are not block multiples — same numerics either way."""
    tile_k = attn_tiles(spec, q.shape[2], k.shape[1])[1]
    if not _attn_kernel_ok(fmt, scale_mode, q.shape[-1], tile_k, block):
        return ref.mx_flash_attention_ref(q, k, v, fmt, spec, block=block,
                                          scale_mode=scale_mode)
    return mx_attn_fwd_pallas(q, k, v, fmt, spec, block=block,
                              interpret=_use_interpret())


@functools.partial(jax.jit, static_argnames=("fmt", "spec", "block",
                                             "scale_mode"))
def mx_flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                           dout: jax.Array, out: jax.Array, lse: jax.Array,
                           fmt: Optional[ElementFormat], spec: AttnSpec,
                           block: int = MX_BLOCK, scale_mode: str = "floor"):
    """Kernel-backed flash-attention dgrad -> (dq, dk, dv)."""
    tile_k = attn_tiles(spec, q.shape[2], k.shape[1])[1]
    if not _attn_kernel_ok(fmt, scale_mode, q.shape[-1], tile_k, block):
        return ref.mx_flash_attention_bwd_ref(q, k, v, dout, out, lse, fmt,
                                              spec, block=block,
                                              scale_mode=scale_mode)
    return mx_attn_bwd_pallas(q, k, v, dout, out, lse, fmt, spec,
                              block=block, interpret=_use_interpret())


@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode"))
def mx_attention_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                        valid: jax.Array, fmt: Optional[ElementFormat],
                        block: int = MX_BLOCK,
                        scale_mode: str = "floor") -> jax.Array:
    """Kernel-backed decode attention: q (BH,G,d) against a (BH,S,·) cache
    with a precomputed (BH,S) bool validity mask (ring-buffer or global
    semantics live entirely in the mask)."""
    d, S = q.shape[-1], k.shape[1]
    if not _attn_kernel_ok(fmt, scale_mode, d, S, block):
        return ref.mx_attention_decode_ref(q, k, v, valid, fmt, block=block,
                                           scale_mode=scale_mode)
    return mx_attn_decode_pallas(q, k, v, valid, fmt, block=block,
                                 interpret=_use_interpret())


@functools.partial(jax.jit, static_argnames=("fmt", "block", "scale_mode"))
def mx_attention_decode_paged(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, page_table: jax.Array,
                              valid: jax.Array,
                              fmt: Optional[ElementFormat],
                              block: int = MX_BLOCK,
                              scale_mode: str = "floor") -> jax.Array:
    """Kernel-backed paged decode: q (BH,G,d) against (N,H,ps,·) page pools
    through a (B,P) page table with a (B, P*ps) per-view validity mask.

    The Pallas path scalar-prefetches the page table so the gather happens
    in the BlockSpec index maps; ineligible shapes (page size or head dim
    not MX-block multiples, non-floor scales) fall back to the gather+slab
    jnp oracle — same numerics either way."""
    d = q.shape[-1]
    ps = k_pool.shape[2]
    S_view = page_table.shape[1] * ps
    if ps % block or not _attn_kernel_ok(fmt, scale_mode, d, S_view, block):
        return ref.mx_attention_decode_paged_ref(
            q, k_pool, v_pool, page_table, valid, fmt, block=block,
            scale_mode=scale_mode)
    return mx_attn_decode_paged_pallas(q, k_pool, v_pool, page_table, valid,
                                       fmt, block=block,
                                       interpret=_use_interpret())


@functools.partial(jax.jit, static_argnames=("fmt_a", "fmt_g", "block"))
def mx_matmul_wgrad(x: jax.Array, dy: jax.Array,
                    fmt_a: Optional[ElementFormat],
                    fmt_g: Optional[ElementFormat],
                    block: int = MX_BLOCK) -> jax.Array:
    """Kernel-backed wgrad ``x (..., K)^T @ dy (..., N)`` -> (K, N).

    Leading (batch/sequence) axes fold into one token axis; both operands
    carry MX blocks along it (the wgrad contraction axis).  Falls back to
    the jnp oracle when the folded token count is not a block multiple."""
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    if x2.shape[0] % block:
        return ref.mx_matmul_wgrad_ref(x2, dy2, fmt_a, fmt_g, block=block)
    return mx_matmul_wgrad_pallas(x2, dy2, fmt_a, fmt_g, block=block,
                                  interpret=_use_interpret())
