"""Pure-jnp oracles for the Pallas kernels.

These delegate to the numerics core (`repro.core.mx`), which is itself
validated against the exact E4M3/E5M2/FP6/FP4 code tables in
tests/test_mx_formats.py — so kernel == ref == code-table, transitively.

The flash-attention oracles double as the *emulation path* for
`mx_contract(..., kind="flash_attn")`: they run the same tiling
(``spec.q_chunk`` × ``spec.kv_chunk``), the same mask/skip predicates, and
the same per-tile op order as the Pallas kernels in mx_attention.py, so
interpret-mode kernel output is bit-identical to the oracle — including
the causal/windowed tile-skipping (`lax.cond`), which reclaims the upper
triangle the roofline flags without waiting for the fused kernel.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attnspec import AttnSpec
from repro.core.formats import ElementFormat
from repro.core.mx import MX_BLOCK, quantize_mx

__all__ = ["mx_quantize_ref", "mx_matmul_ref", "mx_matmul_dgrad_ref",
           "mx_matmul_wgrad_ref", "mx_flash_attention_ref",
           "mx_flash_attention_bwd_ref", "mx_attention_decode_ref",
           "mx_attention_decode_paged_ref", "gather_pages",
           "attn_tile_mask", "attn_tile_needed", "NEG_INF"]

NEG_INF = -1e30


def mx_quantize_ref(x: jax.Array, fmt: ElementFormat, axis: int = -1,
                    block: int = MX_BLOCK,
                    scale_mode: str = "floor") -> jax.Array:
    """Block-scaled quantize-dequantize along ``axis`` (Algorithm 1)."""
    return quantize_mx(x, fmt, axis=axis, block=block, scale_mode=scale_mode)


def mx_matmul_ref(a: jax.Array, b: jax.Array,
                  fmt_a: Optional[ElementFormat],
                  fmt_b: Optional[ElementFormat],
                  block: int = MX_BLOCK) -> jax.Array:
    """MX GEMM oracle: quantize both operands along the contraction axis
    (a: last axis; b: first axis), multiply with fp32 accumulation."""
    aq = quantize_mx(a, fmt_a, axis=-1, block=block)
    bq = quantize_mx(b, fmt_b, axis=0, block=block)
    return jnp.matmul(aq, bq, preferred_element_type=jnp.float32
                      ).astype(a.dtype)


def mx_matmul_dgrad_ref(dy: jax.Array, w: jax.Array,
                        fmt_g: Optional[ElementFormat],
                        fmt_w: Optional[ElementFormat],
                        block: int = MX_BLOCK) -> jax.Array:
    """dgrad oracle: ``dx = Q(dy) @ Q(w)^T`` with MX blocks along N (the
    dgrad contraction axis).  dy: (..., N); w: (K, N) in forward layout."""
    dyq = quantize_mx(dy, fmt_g, axis=-1, block=block)
    wq = quantize_mx(w, fmt_w, axis=1, block=block)
    # Contract the shared N axis in place, as the kernel does: XLA:CPU
    # accumulates a dot on a materialized transpose in another order.
    return jax.lax.dot_general(dyq, wq, (((dyq.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(dy.dtype)


@functools.partial(jax.jit, static_argnames=("fmt_a", "fmt_g", "block"))
def mx_matmul_wgrad_ref(x: jax.Array, dy: jax.Array,
                        fmt_a: Optional[ElementFormat],
                        fmt_g: Optional[ElementFormat],
                        block: int = MX_BLOCK) -> jax.Array:
    """wgrad oracle: ``dW = Q(x^T) @ Q(dy^T)^T`` with MX blocks along T
    (the token/contraction axis).  x: (T, K); dy: (T, N).

    Written, and jitted, so that T is the minor axis of both dot operands,
    as it is for the kernel's in-register transposed tiles: XLA:CPU's
    accumulation order follows the contraction's layout."""
    xq = quantize_mx(x.T, fmt_a, axis=-1, block=block)
    dyq = quantize_mx(dy.T, fmt_g, axis=-1, block=block)
    return jax.lax.dot_general(xq, dyq, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention oracles (canonical folded layout)
# ---------------------------------------------------------------------------
# Layout shared by the oracles, the emulation path, and the Pallas kernels:
#     q:  (BH, G, Tq, d)     BH = batch * kv_heads, G = q heads per kv head
#     k:  (BH, Tk, d)
#     v:  (BH, Tk, dv)
# Forward returns (out (BH, G, Tq, dv) in q.dtype, lse (BH, G, Tq) fp32);
# backward consumes the same residuals the custom VJP stashes.
#
# MX quantization placement (matches the historical emulation scan):
#     QK^T:  q and k blocked along d (the contraction axis)
#     PV:    unnormalized p blocked along the kv tile, v along the kv axis
# Backward is straight-through bf16/fp32 — quantization only appears in the
# *recomputation* of the forward scores s (so p matches forward bitwise);
# dp/ds/dq/dk/dv use raw operands, mirroring "BMM backward stays
# straight-through" in the GEMM pipeline.


def attn_tile_mask(spec: AttnSpec, qi, kj, tile_q: int, tile_k: int,
                   kv_len: int, qpos_iota, kpos_iota):
    """Per-element validity of a (tile_q, tile_k) tile.

    ``qpos_iota``/``kpos_iota`` are (tile_q, tile_k) int32 row/col iotas —
    passed in so the Pallas kernels can supply ``lax.broadcasted_iota`` and
    the jnp path plain ``arange`` broadcasts, with identical values.
    """
    qpos = qi * tile_q + qpos_iota + spec.q_offset
    kpos = kj * tile_k + kpos_iota
    valid = kpos < kv_len
    if spec.kind in ("causal", "window"):
        valid &= qpos >= kpos
    if spec.kind == "window":
        valid &= kpos > qpos - spec.window
    return valid


def attn_tile_needed(spec: AttnSpec, qi, kj, tile_q: int, tile_k: int,
                     kv_len: int):
    """True iff tile (qi, kj) contains any valid position — the skip
    predicate used by both the lax.cond emulation scan and pl.when in the
    kernels.  ``qi``/``kj`` may be traced ints."""
    needed = kj * tile_k < kv_len
    if spec.kind in ("causal", "window"):
        needed &= kj * tile_k <= qi * tile_q + (tile_q - 1) + spec.q_offset
    if spec.kind == "window":
        needed &= ((kj + 1) * tile_k - 1
                   >= qi * tile_q + spec.q_offset - (spec.window - 1))
    return needed


def _iotas(tile_q: int, tile_k: int):
    qpos = jnp.arange(tile_q, dtype=jnp.int32)[:, None]
    kpos = jnp.arange(tile_k, dtype=jnp.int32)[None, :]
    return (jnp.broadcast_to(qpos, (tile_q, tile_k)),
            jnp.broadcast_to(kpos, (tile_q, tile_k)))


def _attn_tiles(spec: AttnSpec, Tq: int, Tk: int):
    tile_q = min(spec.q_chunk, Tq)
    tile_k = min(spec.kv_chunk, Tk)
    nq = -(-Tq // tile_q)
    nk = -(-Tk // tile_k)
    return tile_q, tile_k, nq, nk


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def mx_flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                           fmt: Optional[ElementFormat], spec: AttnSpec,
                           block: int = MX_BLOCK,
                           scale_mode: str = "floor"):
    """Online-softmax flash attention with MX-quantized QK^T / PV products
    and causal/window tile-skipping (lax.cond) — the semantic oracle the
    Pallas forward kernel must match bitwise in interpret mode."""
    BH, G, Tq, d = q.shape
    Tk = k.shape[1]
    dv = v.shape[-1]
    tile_q, tile_k, nq, nk = _attn_tiles(spec, Tq, Tk)
    scale = 1.0 / math.sqrt(d)
    qp = _pad_axis(q.astype(jnp.float32), 2, nq * tile_q)
    kp = _pad_axis(k.astype(jnp.float32), 1, nk * tile_k)
    vp = _pad_axis(v.astype(jnp.float32), 1, nk * tile_k)
    # (n_tiles, BH, ...) tile-major stacks for the scans.
    qc = qp.reshape(BH, G, nq, tile_q, d).transpose(2, 0, 1, 3, 4)
    kc = kp.reshape(BH, nk, tile_k, d).transpose(1, 0, 2, 3)
    vc = vp.reshape(BH, nk, tile_k, dv).transpose(1, 0, 2, 3)
    qpos_iota, kpos_iota = _iotas(tile_q, tile_k)

    def q_step(_, qi_qt):
        qi, qt = qi_qt
        qq = quantize_mx(qt, fmt, axis=-1, block=block,
                         scale_mode=scale_mode)
        m0 = jnp.full((BH, G, tile_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((BH, G, tile_q), jnp.float32)
        a0 = jnp.zeros((BH, G, tile_q, dv), jnp.float32)

        def kv_step(carry, kj_kt_vt):
            kj, kt, vt = kj_kt_vt

            def compute(carry):
                m, l, acc = carry
                kk = quantize_mx(kt, fmt, axis=-1, block=block,
                                 scale_mode=scale_mode)
                s = jnp.einsum("bgqd,bkd->bgqk", qq, kk,
                               preferred_element_type=jnp.float32) * scale
                valid = attn_tile_mask(spec, qi, kj, tile_q, tile_k, Tk,
                                       qpos_iota, kpos_iota)
                s = jnp.where(valid, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                # Guard: fully-masked rows keep p == 0 instead of
                # exp(NEG_INF - NEG_INF) == 1, so computing a masked tile
                # is bitwise identical to skipping it.
                p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pq = quantize_mx(p, fmt, axis=-1, block=block,
                                 scale_mode=scale_mode)
                vv = quantize_mx(vt, fmt, axis=-2, block=block,
                                 scale_mode=scale_mode)
                pv = jnp.einsum("bgqk,bkd->bgqd", pq, vv,
                                preferred_element_type=jnp.float32)
                acc_new = acc * corr[..., None] + pv
                return m_new, l_new, acc_new

            needed = attn_tile_needed(spec, qi, kj, tile_q, tile_k, Tk)
            return jax.lax.cond(needed, compute, lambda c: c, carry), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out, lse)

    _, (out, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    out = out.transpose(1, 2, 0, 3, 4).reshape(BH, G, nq * tile_q, dv)
    lse = lse.transpose(1, 2, 0, 3).reshape(BH, G, nq * tile_q)
    return out[:, :, :Tq], lse[:, :, :Tq]


def mx_flash_attention_bwd_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                               dout: jax.Array, out: jax.Array,
                               lse: jax.Array,
                               fmt: Optional[ElementFormat], spec: AttnSpec,
                               block: int = MX_BLOCK,
                               scale_mode: str = "floor"):
    """Flash-attention dgrad oracle: recompute probabilities from the
    (quantized) scores and the stashed lse, then accumulate dQ over kv
    tiles and dK/dV over q tiles — the same two-pass structure and tile
    skipping as the Pallas dq/dkv kernels."""
    BH, G, Tq, d = q.shape
    Tk = k.shape[1]
    dv = v.shape[-1]
    tile_q, tile_k, nq, nk = _attn_tiles(spec, Tq, Tk)
    scale = 1.0 / math.sqrt(d)
    dof = dout.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (BH, G, Tq)
    qp = _pad_axis(q.astype(jnp.float32), 2, nq * tile_q)
    dop = _pad_axis(dof, 2, nq * tile_q)
    lsep = _pad_axis(lse, 2, nq * tile_q)
    dlp = _pad_axis(delta, 2, nq * tile_q)
    kp = _pad_axis(k.astype(jnp.float32), 1, nk * tile_k)
    vp = _pad_axis(v.astype(jnp.float32), 1, nk * tile_k)
    qc = qp.reshape(BH, G, nq, tile_q, d).transpose(2, 0, 1, 3, 4)
    doc = dop.reshape(BH, G, nq, tile_q, dv).transpose(2, 0, 1, 3, 4)
    lsec = lsep.reshape(BH, G, nq, tile_q).transpose(2, 0, 1, 3)
    dlc = dlp.reshape(BH, G, nq, tile_q).transpose(2, 0, 1, 3)
    kc = kp.reshape(BH, nk, tile_k, d).transpose(1, 0, 2, 3)
    vc = vp.reshape(BH, nk, tile_k, dv).transpose(1, 0, 2, 3)
    qpos_iota, kpos_iota = _iotas(tile_q, tile_k)

    def tile_p_ds(qq, kt, vt, dot, lset, dlt, qi, kj):
        """Shared per-tile recomputation: (p, ds*scale) for tile (qi, kj)."""
        kk = quantize_mx(kt, fmt, axis=-1, block=block,
                         scale_mode=scale_mode)
        s = jnp.einsum("bgqd,bkd->bgqk", qq, kk,
                       preferred_element_type=jnp.float32) * scale
        valid = attn_tile_mask(spec, qi, kj, tile_q, tile_k, Tk,
                               qpos_iota, kpos_iota)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.where(valid, jnp.exp(s - lset[..., None]), 0.0)
        dp = jnp.einsum("bgqd,bkd->bgqk", dot, vt,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - dlt[..., None]) * scale
        return p, ds

    # Pass 1: dQ — for each q tile, accumulate over kv tiles.
    def dq_step(_, qi_tiles):
        qi, qt, dot, lset, dlt = qi_tiles
        qq = quantize_mx(qt, fmt, axis=-1, block=block,
                         scale_mode=scale_mode)

        def kv_step(dq_acc, kj_kt_vt):
            kj, kt, vt = kj_kt_vt

            def compute(dq_acc):
                _, ds = tile_p_ds(qq, kt, vt, dot, lset, dlt, qi, kj)
                return dq_acc + jnp.einsum(
                    "bgqk,bkd->bgqd", ds, kt,
                    preferred_element_type=jnp.float32)

            needed = attn_tile_needed(spec, qi, kj, tile_q, tile_k, Tk)
            return jax.lax.cond(needed, compute, lambda a: a, dq_acc), None

        dq_acc, _ = jax.lax.scan(
            kv_step, jnp.zeros((BH, G, tile_q, d), jnp.float32),
            (jnp.arange(nk), kc, vc))
        return None, dq_acc

    _, dq = jax.lax.scan(dq_step, None, (jnp.arange(nq), qc, doc, lsec, dlc))
    dq = dq.transpose(1, 2, 0, 3, 4).reshape(BH, G, nq * tile_q, d)

    # Pass 2: dK/dV — for each kv tile, accumulate over q tiles, keeping a
    # per-g partial; the G reduction happens after the scan (same jnp.sum
    # as the kernel wrapper, so both paths share the reduction order).
    def dkv_step(_, kj_tiles):
        kj, kt, vt = kj_tiles

        def q_step(carry, qi_tiles):
            qi, qt, dot, lset, dlt = qi_tiles

            def compute(carry):
                dk_acc, dv_acc = carry
                qq = quantize_mx(qt, fmt, axis=-1, block=block,
                                 scale_mode=scale_mode)
                p, ds = tile_p_ds(qq, kt, vt, dot, lset, dlt, qi, kj)
                dv_new = dv_acc + jnp.einsum(
                    "bgqk,bgqd->bgkd", p, dot,
                    preferred_element_type=jnp.float32)
                dk_new = dk_acc + jnp.einsum(
                    "bgqk,bgqd->bgkd", ds, qt,
                    preferred_element_type=jnp.float32)
                return dk_new, dv_new

            needed = attn_tile_needed(spec, qi, kj, tile_q, tile_k, Tk)
            return jax.lax.cond(needed, compute, lambda c: c, carry), None

        carry0 = (jnp.zeros((BH, G, tile_k, d), jnp.float32),
                  jnp.zeros((BH, G, tile_k, dv), jnp.float32))
        (dk_g, dv_g), _ = jax.lax.scan(
            q_step, carry0, (jnp.arange(nq), qc, doc, lsec, dlc))
        return None, (dk_g, dv_g)

    _, (dk_g, dv_g) = jax.lax.scan(dkv_step, None, (jnp.arange(nk), kc, vc))
    dk_g = dk_g.transpose(1, 2, 0, 3, 4).reshape(BH, G, nk * tile_k, d)
    dv_g = dv_g.transpose(1, 2, 0, 3, 4).reshape(BH, G, nk * tile_k, dv)
    dq = dq[:, :, :Tq].astype(q.dtype)
    dk = jnp.sum(dk_g[:, :, :Tk], axis=1).astype(k.dtype)
    dv = jnp.sum(dv_g[:, :, :Tk], axis=1).astype(v.dtype)
    return dq, dk, dv


def mx_attention_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid: jax.Array,
                            fmt: Optional[ElementFormat],
                            block: int = MX_BLOCK,
                            scale_mode: str = "floor") -> jax.Array:
    """Decode-shaped (Tq=1) oracle.  q: (BH, G, d); k: (BH, S, d);
    v: (BH, S, dv); valid: (BH, S) bool — per-slot validity computed by the
    caller (ring-buffer age or global `kpos <= pos`), shared verbatim with
    the Pallas decode kernel.  Normalized probabilities are quantized along
    the full cache axis, matching the historical decode emulation."""
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    qq = quantize_mx(q.astype(jnp.float32), fmt, axis=-1, block=block,
                     scale_mode=scale_mode)
    kk = quantize_mx(k.astype(jnp.float32), fmt, axis=-1, block=block,
                     scale_mode=scale_mode)
    s = jnp.einsum("bgd,bsd->bgs", qq, kk,
                   preferred_element_type=jnp.float32) * scale
    ok = valid[:, None, :]
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pr = p / jnp.maximum(l, 1e-30)
    prq = quantize_mx(pr, fmt, axis=-1, block=block, scale_mode=scale_mode)
    vv = quantize_mx(v.astype(jnp.float32), fmt, axis=-2, block=block,
                     scale_mode=scale_mode)
    out = jnp.einsum("bgs,bsd->bgd", prq, vv,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def gather_pages(pool: jax.Array, page_table: jax.Array,
                 n_kv: int) -> jax.Array:
    """Assemble the folded (B*H, P*ps, d) contiguous view of a page pool.

    pool: (N, H, ps, d) global page pool (H = n_kv heads); page_table:
    (B, P) int32, negatives = unallocated (the gather clamps them to page
    0 — callers mask those view positions out via ``valid``).  Logical
    position ``t`` of request ``b`` lives at view position ``t`` exactly:
    page ``t // ps``, offset ``t % ps``."""
    B, P = page_table.shape
    N, H, ps, d = pool.shape
    ptc = jnp.clip(page_table, 0, N - 1)
    g = pool[ptc]                                  # (B, P, H, ps, d)
    return g.transpose(0, 2, 1, 3, 4).reshape(B * H, P * ps, d)


def mx_attention_decode_paged_ref(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, page_table: jax.Array,
                                  valid: jax.Array,
                                  fmt: Optional[ElementFormat],
                                  block: int = MX_BLOCK,
                                  scale_mode: str = "floor") -> jax.Array:
    """Paged decode oracle: gather pages into the contiguous slab view and
    run the slab decode oracle on it — the paging transform is *only* a
    gather, so paged output is bitwise equal to slab output whenever the
    gathered view holds the same values.

    q: (BH, G, d) with BH = B * n_kv; k_pool/v_pool: (N, H, ps, dk/dv);
    page_table: (B, P) int32; valid: (B, P*ps) bool per *view* position
    (allocated page AND logical position <= pos)."""
    B = page_table.shape[0]
    H = q.shape[0] // B
    kv = gather_pages(k_pool, page_table, H)
    vv = gather_pages(v_pool, page_table, H)
    validr = jnp.repeat(valid, H, axis=0)
    return mx_attention_decode_ref(q, kv, vv, validr, fmt, block=block,
                                   scale_mode=scale_mode)
