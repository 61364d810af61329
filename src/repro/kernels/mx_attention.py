"""Pallas TPU kernels: MX-quantized flash attention (fwd / dgrad / decode).

The attention analogue of mx_matmul / mx_matmul_bwd: both BMMs of the
attention step run in MX precision with quantize-on-load — tiles are
quantized *after* the HBM->VMEM copy (q/k blocked along the head dim, the
unnormalized probabilities and v along the kv axis) and fed to the MXU in
dequantized form with fp32 VMEM accumulators.  This is the
quantization placement of NVIDIA's MXFP8 pre-training recipe
(arXiv:2506.08027) for attention-score BMMs, mapped onto TPU memory
spaces.

Canonical folded layout (shared with ref.py and the emulation scan):

    q:  (BH, G, Tq, d)     BH = batch * kv_heads, G = q heads per kv head
    k:  (BH, Tk, d)
    v:  (BH, Tk, dv)

Forward runs an online-softmax m/l/acc carry over the kv grid dimension
(grid (BH, G, nq, nk), kv innermost) and skips tiles the AttnSpec mask
fully excludes — ``attn_tile_needed`` guards the whole tile body with
``pl.when``, so masked causal/windowed (q, kv) tiles are never computed.
The guarded probability update (``p = where(valid, exp(s - m_new), 0)``)
makes computing a fully-masked tile bitwise identical to skipping it,
which is what keeps the kernel bit-identical to the lax.cond-skipping
oracle in interpret mode.

Backward is the two-pass flash dgrad: a dQ kernel accumulating over kv
tiles and a dK/dV kernel accumulating over q tiles (per-g partials; the G
reduction happens in the jnp wrapper so both paths share one reduction
order).  Probabilities are *recomputed* from the quantized scores and the
stashed logsumexp; the gradient products themselves are straight-through
(raw operands), mirroring the GEMM pipeline's backward.

The decode kernel is the Tq=1 serve-path shape: one (G, S) score tile per
(batch*kv_head), explicit softmax, normalized probabilities quantized
along the full cache axis.  Ring-buffer/global cache validity is a
precomputed (BH, S) mask argument — the same array feeds the oracle, so
ring semantics cannot drift between paths.

Tile sizes come from AttnSpec.q_chunk/kv_chunk (the emulation chunk
sizes), so tile-local MX block scales equal whole-operand block scales
whenever d and the kv tile are block multiples — the wrappers in ops.py
fall back to the oracle otherwise.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.attnspec import AttnSpec
from repro.core.formats import ElementFormat
from repro.core.mx import MX_BLOCK
from .mx_quant import _quantize_block_tile
from .ref import NEG_INF, attn_tile_mask, attn_tile_needed

__all__ = ["mx_attn_fwd_pallas", "mx_attn_bwd_pallas",
           "mx_attn_decode_pallas", "mx_attn_decode_paged_pallas",
           "attn_tiles"]


def attn_tiles(spec: AttnSpec, Tq: int, Tk: int):
    """(tile_q, tile_k, nq, nk) for a given spec and true sequence lengths
    — shared with ref.py so both paths tile identically."""
    tile_q = min(spec.q_chunk, Tq)
    tile_k = min(spec.kv_chunk, Tk)
    return tile_q, tile_k, -(-Tq // tile_q), -(-Tk // tile_k)


def _tile_iotas(tile_q: int, tile_k: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0),
            jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1))


def _quant(x, fmt, block):
    """Quantize a 2D tile with MX blocks along its last axis."""
    return x if fmt is None else _quantize_block_tile(x, fmt, block)


def _quant_rows(x, fmt, block):
    """Quantize a 2D tile with MX blocks along its *first* axis (the
    transpose in/out of the row-blocked quantizer stays in VREGs)."""
    return x if fmt is None else _quantize_block_tile(x.T, fmt, block).T


def _scores(q_ref, k_ref, i, j, spec, fmt, block, kv_len, scale):
    """Shared per-tile score recomputation: quantized QK^T, masked."""
    tile_q = q_ref.shape[-2]
    tile_k = k_ref.shape[-2]
    qt = q_ref[0, 0].astype(jnp.float32)
    kt = k_ref[0].astype(jnp.float32)
    qq = _quant(qt, fmt, block)
    kk = _quant(kt, fmt, block)
    s = jax.lax.dot_general(qq, kk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos_iota, kpos_iota = _tile_iotas(tile_q, tile_k)
    valid = attn_tile_mask(spec, i, j, tile_q, tile_k, kv_len,
                           qpos_iota, kpos_iota)
    return jnp.where(valid, s, NEG_INF), valid, qt, kt


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _mx_attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                        m_scr, l_scr, acc_scr, *,
                        fmt: Optional[ElementFormat], block: int,
                        spec: AttnSpec, kv_len: int, n_k: int, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    tile_q, tile_k = q_ref.shape[-2], k_ref.shape[-2]

    @pl.when(attn_tile_needed(spec, i, j, tile_q, tile_k, kv_len))
    def _compute():
        s, valid, _, _ = _scores(q_ref, k_ref, i, j, spec, fmt, block,
                                 kv_len, scale)
        vt = v_ref[0].astype(jnp.float32)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Guard: fully-masked rows keep p == 0 instead of
        # exp(NEG_INF - NEG_INF) == 1 — computing a masked tile is then
        # bitwise identical to skipping it.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pq = _quant(p, fmt, block)            # blocks along the kv tile
        vv = _quant_rows(vt, fmt, block)      # blocks along the kv axis
        pv = jax.lax.dot_general(pq, vv, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_k - 1)
    def _done():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)
                       ).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30))


@functools.partial(jax.jit, static_argnames=(
    "fmt", "spec", "block", "interpret"))
def mx_attn_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       fmt: Optional[ElementFormat], spec: AttnSpec,
                       block: int = MX_BLOCK,
                       interpret: bool = False):
    """Flash-attention forward.  Returns (out (BH,G,Tq,dv) in q.dtype,
    lse (BH,G,Tq) fp32).  d and the kv tile must be block multiples when
    ``fmt`` is set (ops.py guards this)."""
    BH, G, Tq, d = q.shape
    Tk = k.shape[1]
    dv = v.shape[-1]
    tile_q, tile_k, nq, nk = attn_tiles(spec, Tq, Tk)
    scale = 1.0 / math.sqrt(d)
    pq_, pk_ = nq * tile_q - Tq, nk * tile_k - Tk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq_), (0, 0))) if pq_ else q
    kp = jnp.pad(k, ((0, 0), (0, pk_), (0, 0))) if pk_ else k
    vp = jnp.pad(v, ((0, 0), (0, pk_), (0, 0))) if pk_ else v
    out, lse = pl.pallas_call(
        functools.partial(_mx_attn_fwd_kernel, fmt=fmt, block=block,
                          spec=spec, kv_len=Tk, n_k=nk, scale=scale),
        grid=(BH, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, d), lambda b, g, i, j: (b, g, i, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, g, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_k, dv), lambda b, g, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tile_q, dv), lambda b, g, i, j: (b, g, i, 0)),
            pl.BlockSpec((1, 1, tile_q, 1), lambda b, g, i, j: (b, g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, G, Tq + pq_, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, G, Tq + pq_, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_q, 128), jnp.float32),
                        pltpu.VMEM((tile_q, 128), jnp.float32),
                        pltpu.VMEM((tile_q, dv), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Tq], lse[:, :, :Tq, 0]


# ---------------------------------------------------------------------------
# Backward: dQ pass (accumulate over kv tiles) + dK/dV pass (over q tiles)
# ---------------------------------------------------------------------------
def _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, i, j, *,
          spec, fmt, block, kv_len, scale):
    """Shared backward tile recomputation: (p, ds*scale, raw q, raw k)."""
    s, valid, qt, kt = _scores(q_ref, k_ref, i, j, spec, fmt, block,
                               kv_len, scale)
    vt = v_ref[0].astype(jnp.float32)
    dot = do_ref[0, 0].astype(jnp.float32)
    lset = lse_ref[0, 0]     # (tile_q, 1)
    dlt = dl_ref[0, 0]       # (tile_q, 1)
    p = jnp.where(valid, jnp.exp(s - lset), 0.0)
    dp = jax.lax.dot_general(dot, vt, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dlt) * scale
    return p, ds, qt, kt, dot


def _mx_attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                       dq_ref, acc_scr, *,
                       fmt: Optional[ElementFormat], block: int,
                       spec: AttnSpec, kv_len: int, n_k: int, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    tile_q, tile_k = q_ref.shape[-2], k_ref.shape[-2]

    @pl.when(attn_tile_needed(spec, i, j, tile_q, tile_k, kv_len))
    def _compute():
        _, ds, _, kt, _ = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                dl_ref, i, j, spec=spec, fmt=fmt,
                                block=block, kv_len=kv_len, scale=scale)
        acc_scr[...] += jax.lax.dot_general(
            ds, kt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _done():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _mx_attn_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *,
                        fmt: Optional[ElementFormat], block: int,
                        spec: AttnSpec, kv_len: int, n_q: int, scale: float):
    j, i = pl.program_id(2), pl.program_id(3)   # kv tile outer, q innermost

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    tile_q, tile_k = q_ref.shape[-2], k_ref.shape[-2]

    @pl.when(attn_tile_needed(spec, i, j, tile_q, tile_k, kv_len))
    def _compute():
        p, ds, qt, _, dot = _p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  dl_ref, i, j, spec=spec, fmt=fmt,
                                  block=block, kv_len=kv_len, scale=scale)
        dv_scr[...] += jax.lax.dot_general(
            p, dot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, qt, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _done():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "fmt", "spec", "block", "interpret"))
def mx_attn_bwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       dout: jax.Array, out: jax.Array, lse: jax.Array,
                       fmt: Optional[ElementFormat], spec: AttnSpec,
                       block: int = MX_BLOCK,
                       interpret: bool = False):
    """Flash-attention dgrad: (dq, dk, dv) in operand dtypes."""
    BH, G, Tq, d = q.shape
    Tk = k.shape[1]
    dv_ = v.shape[-1]
    tile_q, tile_k, nq, nk = attn_tiles(spec, Tq, Tk)
    scale = 1.0 / math.sqrt(d)
    dof = dout.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (BH, G, Tq)
    pq_, pk_ = nq * tile_q - Tq, nk * tile_k - Tk

    def padq(x):
        return (jnp.pad(x, ((0, 0), (0, 0), (0, pq_)) + ((0, 0),) *
                        (x.ndim - 3)) if pq_ else x)

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, pk_), (0, 0))) if pk_ else x

    qp, dop = padq(q), padq(dof)
    lsep, dlp = padq(lse)[..., None], padq(delta)[..., None]
    kp, vp = padk(k), padk(v)
    q_spec = pl.BlockSpec((1, 1, tile_q, d), lambda b, g, x, y: (b, g, x, 0))
    do_spec = pl.BlockSpec((1, 1, tile_q, dv_),
                           lambda b, g, x, y: (b, g, x, 0))
    r_spec = pl.BlockSpec((1, 1, tile_q, 1), lambda b, g, x, y: (b, g, x, 0))
    k_spec = pl.BlockSpec((1, tile_k, d), lambda b, g, x, y: (b, y, 0))
    v_spec = pl.BlockSpec((1, tile_k, dv_), lambda b, g, x, y: (b, y, 0))
    dq = pl.pallas_call(
        functools.partial(_mx_attn_dq_kernel, fmt=fmt, block=block,
                          spec=spec, kv_len=Tk, n_k=nk, scale=scale),
        grid=(BH, G, nq, nk),
        in_specs=[q_spec, k_spec, v_spec, do_spec, r_spec, r_spec],
        out_specs=pl.BlockSpec((1, 1, tile_q, d),
                               lambda b, g, x, y: (b, g, x, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, Tq + pq_, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((tile_q, d), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dlp)
    # dK/dV pass: grid transposed so the q dimension is innermost; the
    # index maps swap (x, y) accordingly (x = kv tile, y = q tile).
    kq_spec = pl.BlockSpec((1, 1, tile_q, d), lambda b, g, x, y: (b, g, y, 0))
    kdo_spec = pl.BlockSpec((1, 1, tile_q, dv_),
                            lambda b, g, x, y: (b, g, y, 0))
    kr_spec = pl.BlockSpec((1, 1, tile_q, 1),
                           lambda b, g, x, y: (b, g, y, 0))
    kk_spec = pl.BlockSpec((1, tile_k, d), lambda b, g, x, y: (b, x, 0))
    kv_spec = pl.BlockSpec((1, tile_k, dv_), lambda b, g, x, y: (b, x, 0))
    dk_g, dv_g = pl.pallas_call(
        functools.partial(_mx_attn_dkv_kernel, fmt=fmt, block=block,
                          spec=spec, kv_len=Tk, n_q=nq, scale=scale),
        grid=(BH, G, nk, nq),
        in_specs=[kq_spec, kk_spec, kv_spec, kdo_spec, kr_spec, kr_spec],
        out_specs=[
            pl.BlockSpec((1, 1, tile_k, d), lambda b, g, x, y: (b, g, x, 0)),
            pl.BlockSpec((1, 1, tile_k, dv_),
                         lambda b, g, x, y: (b, g, x, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, G, Tk + pk_, d), jnp.float32),
            jax.ShapeDtypeStruct((BH, G, Tk + pk_, dv_), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_k, d), jnp.float32),
                        pltpu.VMEM((tile_k, dv_), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dlp)
    dq = dq[:, :, :Tq].astype(q.dtype)
    dk = jnp.sum(dk_g[:, :, :Tk], axis=1).astype(k.dtype)
    dv = jnp.sum(dv_g[:, :, :Tk], axis=1).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Decode (Tq = 1)
# ---------------------------------------------------------------------------
def _mx_attn_decode_kernel(q_ref, k_ref, v_ref, msk_ref, o_ref, *,
                           fmt: Optional[ElementFormat], block: int,
                           scale: float):
    o_ref[0] = _mx_attn_decode_body(
        q_ref[0].astype(jnp.float32),       # (G, d)
        k_ref[0].astype(jnp.float32),       # (S, d)
        v_ref[0].astype(jnp.float32),       # (S, dv)
        msk_ref[0] != 0,                    # (1, S)
        fmt=fmt, block=block, scale=scale, out_dtype=o_ref.dtype)


def _mx_attn_decode_body(qt, kt, vt, ok, *, fmt, block, scale, out_dtype):
    """Shared decode compute (explicit softmax over the full cache view) —
    called on contiguous slab tiles and on the page-assembled scratch alike
    so the two kernels cannot drift numerically."""
    qq = _quant(qt, fmt, block)
    kk = _quant(kt, fmt, block)
    s = jax.lax.dot_general(qq, kk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pr = p / jnp.maximum(l, 1e-30)
    prq = _quant(pr, fmt, block)            # blocks along the cache axis
    vv = _quant_rows(vt, fmt, block)        # blocks along the cache axis
    return jax.lax.dot_general(
        prq, vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_dtype)


def _mx_attn_decode_paged_kernel(ptc_ref, q_ref, k_ref, v_ref, msk_ref,
                                 o_ref, k_scr, v_scr, *,
                                 fmt: Optional[ElementFormat], block: int,
                                 scale: float, ps: int, n_pages: int):
    """Grid (BH, P): the page dimension is innermost, so each step copies
    one gathered page of one kv head (the BlockSpec index map did the
    page-table lookup) into the VMEM scratch slab; the last page step runs
    the exact slab decode body on the assembled (S_view, ·) scratch —
    bitwise equal to gathering on the host and calling the slab kernel."""
    del ptc_ref  # consumed by the BlockSpec index maps
    p = pl.program_id(1)
    k_scr[pl.ds(p * ps, ps), :] = k_ref[0, 0].astype(jnp.float32)
    v_scr[pl.ds(p * ps, ps), :] = v_ref[0, 0].astype(jnp.float32)

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = _mx_attn_decode_body(
            q_ref[0].astype(jnp.float32), k_scr[...], v_scr[...],
            msk_ref[0] != 0, fmt=fmt, block=block, scale=scale,
            out_dtype=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def mx_attn_decode_paged_pallas(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, page_table: jax.Array,
                                valid: jax.Array,
                                fmt: Optional[ElementFormat],
                                block: int = MX_BLOCK,
                                interpret: bool = False) -> jax.Array:
    """Paged decode: q (BH, G, d) with BH = B * H against page pools
    k_pool/v_pool (N, H, ps, ·) through a (B, P) page table.

    The page table rides in as a scalar-prefetch operand, so the k/v
    BlockSpec index maps resolve physical pages *before* the DMA — the
    kernel itself never indexes HBM.  Head-major pools make each DMA one
    (ps, d) tile of one head, a legal (8, 128)-tiled block, and keep the
    VMEM scratch at one head's (S_view, d) view.  valid: (B, P*ps) bool
    per view position (unallocated pages are clamped to page 0 by the
    gather and masked here, exactly like the ref oracle)."""
    BH, G, d = q.shape
    B, P = page_table.shape
    H = BH // B
    N, _, ps, dk = k_pool.shape
    dv_ = v_pool.shape[-1]
    S_view = P * ps
    scale = 1.0 / math.sqrt(d)
    ptc = jnp.clip(page_table, 0, N - 1).astype(jnp.int32)
    msk = valid.astype(jnp.int32)[:, None, :]              # (B, 1, S_view)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, P),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda bh, p, pt: (bh, 0, 0)),
            pl.BlockSpec((1, 1, ps, dk),
                         lambda bh, p, pt: (pt[bh // H, p], bh % H, 0, 0)),
            pl.BlockSpec((1, 1, ps, dv_),
                         lambda bh, p, pt: (pt[bh // H, p], bh % H, 0, 0)),
            pl.BlockSpec((1, 1, S_view), lambda bh, p, pt: (bh // H, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, dv_), lambda bh, p, pt: (bh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((S_view, dk), jnp.float32),
                        pltpu.VMEM((S_view, dv_), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mx_attn_decode_paged_kernel, fmt=fmt, block=block,
                          scale=scale, ps=ps, n_pages=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, G, dv_), q.dtype),
        interpret=interpret,
    )(ptc, q, k_pool, v_pool, msk)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def mx_attn_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                          valid: jax.Array,
                          fmt: Optional[ElementFormat],
                          block: int = MX_BLOCK,
                          interpret: bool = False) -> jax.Array:
    """Decode-shaped attention: q (BH,G,d) against a (BH,S,·) cache with a
    precomputed (BH,S) bool validity mask (ring/global semantics live in
    the mask, not the kernel)."""
    BH, G, d = q.shape
    S = k.shape[1]
    dv_ = v.shape[-1]
    scale = 1.0 / math.sqrt(d)
    msk = valid.astype(jnp.int32)[:, None, :]    # (BH, 1, S)
    return pl.pallas_call(
        functools.partial(_mx_attn_decode_kernel, fmt=fmt, block=block,
                          scale=scale),
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, dv_), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, dv_), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, dv_), q.dtype),
        interpret=interpret,
    )(q, k, v, msk)
