"""Attention: GQA/MQA/MHA with QK-norm and RoPE, via mx_contract.

The score/value BMMs are MX-quantized when ``qcfg.attn`` is set (the MX
emulation library quantizes MatMul/BMM inputs); softmax runs in fp32.
The q/k/v/o *projections* go through `qdense` -> ``mx_contract(kind=
"dense")``, whose custom VJP routes their forward, dgrad, and wgrad GEMMs
to the fused Pallas kernels in the per-pass formats of ``qcfg``.

Attention *mixing* routes through ``mx_contract(kind="flash_attn")`` /
``"attn_decode"`` on the folded (BH, G, T, d) layout: on the fused path
that is the flash-attention Pallas kernel family (mx_attention.py) with
online softmax, causal/window tile-skipping, and a hand-written flash
dgrad; on the emulation path it is the bit-identical jnp oracle
(kernels/ref.py) — masked causal KV tiles are skipped there too
(lax.cond), so the CPU baseline no longer computes the upper triangle the
roofline used to flag.  Mask kind, window, chunk/tile sizes, and cache
geometry all come from a single :class:`~repro.core.AttnSpec`.

Attention gradients are quantized at the projection GEMMs (the dominant
cost); the flash backward recomputes probabilities from the quantized
scores but keeps its gradient products straight-through bf16.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import AttnSpec, QuantConfig, mx_contract, quantize_mx

__all__ = ["attn_init", "attention", "attention_decode",
           "attention_decode_paged", "attention_prefill",
           "attention_prefill_chunk", "flash_attention", "local_attention",
           "paged_valid_mask"]

NEG_INF = -1e30


def _maybe_quant(x, qcfg: QuantConfig, axis: int):
    if not qcfg.attn or qcfg.a_fwd is None:
        return x
    return quantize_mx(x, qcfg.a_fwd, axis=axis, block=qcfg.block,
                       scale_mode=qcfg.scale_mode)


def attn_init(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
              qk_norm: bool = False, qkv_bias: bool = False, n_layers: int = 1):
    from .layers import dense_init, norm_init
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * d_head, bias=qkv_bias),
        "wk": dense_init(ks[1], d_model, n_kv * d_head, bias=qkv_bias),
        "wv": dense_init(ks[2], d_model, n_kv * d_head, bias=qkv_bias),
        "wo": dense_init(ks[3], n_heads * d_head, d_model,
                         std=1.0 / math.sqrt(n_heads * d_head * 2 * n_layers)),
    }
    if qk_norm:
        p["q_norm"] = norm_init(d_head)
        p["k_norm"] = norm_init(d_head)
    return p


def _project_qkv(p, x, xkv, qcfg, n_heads, n_kv, d_head, positions,
                 kv_positions=None, rope_theta=1e4, use_rope=True):
    from .layers import apply_norm, qdense, rope
    B, T = x.shape[:2]
    Tk = xkv.shape[1]
    G = n_heads // n_kv
    q = qdense(p["wq"], x, qcfg).reshape(B, T, n_kv, G, d_head)
    k = qdense(p["wk"], xkv, qcfg).reshape(B, Tk, n_kv, 1, d_head)
    v = qdense(p["wv"], xkv, qcfg).reshape(B, Tk, n_kv, 1, d_head)
    if "q_norm" in p:
        q = apply_norm(p["q_norm"], q, qcfg.without_ln_quant())
        k = apply_norm(p["k_norm"], k, qcfg.without_ln_quant())
    if use_rope:
        kv_positions = positions if kv_positions is None else kv_positions
        q = rope(q, positions, rope_theta)
        k = rope(k, kv_positions, rope_theta)
    return q, k[:, :, :, 0], v[:, :, :, 0]


def _fold(q, k, v):
    """(B, T, Hkv, G/·, d) model layout -> the canonical kernel layout
    q (B*Hkv, G, Tq, d), k (B*Hkv, Tk, d), v (B*Hkv, Tk, dv)."""
    B, Tq, Hkv, G, d = q.shape
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * Hkv, G, Tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, k.shape[1], k.shape[-1])
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, v.shape[1], v.shape[-1])
    return qf, kf, vf


def _unfold(out, B, Hkv):
    """(B*Hkv, G, Tq, dv) -> (B, Tq, Hkv, G, dv)."""
    BH, G, Tq, dv = out.shape
    return out.reshape(B, Hkv, G, Tq, dv).transpose(0, 3, 1, 2, 4)


def flash_attention(q, k, v, qcfg: QuantConfig,
                    spec: Optional[AttnSpec] = None, *,
                    causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 1024, q_offset: int = 0) -> jax.Array:
    """Exact attention with online softmax and masked-tile skipping.

    q: (B, Tq, Hkv, G, d); k: (B, Tk, Hkv, d); v: (B, Tk, Hkv, dv).
    Returns (B, Tq, Hkv, G, dv).  Pass ``spec`` (an AttnSpec) to select
    mask kind and tiling; the bare ``causal``/``q_chunk``/``kv_chunk``/
    ``q_offset`` kwargs are the deprecated pre-AttnSpec signature.
    """
    if spec is None:
        warnings.warn(
            "flash_attention(..., causal=, q_chunk=, ...) kwargs are "
            "deprecated; pass spec=AttnSpec.training(...)",
            DeprecationWarning, stacklevel=2)
        spec = AttnSpec.training(causal=causal, q_chunk=q_chunk,
                                 kv_chunk=kv_chunk, q_offset=q_offset)
    B, Hkv = q.shape[0], q.shape[2]
    qf, kf, vf = _fold(q, k, v)
    out = mx_contract(qf, (kf, vf), qcfg, kind="flash_attn", spec=spec)
    return _unfold(out, B, Hkv)


def local_attention(q, k, v, qcfg: QuantConfig, window: int) -> jax.Array:
    """Deprecated: causal sliding-window attention is now the
    ``kind="window"`` mask of :func:`flash_attention` (tile-skipped online
    softmax, O(T·W) compute once tiles outside the window are skipped)."""
    warnings.warn(
        "local_attention is deprecated; use flash_attention with "
        "spec=AttnSpec.training(window=...)",
        DeprecationWarning, stacklevel=2)
    return flash_attention(q, k, v, qcfg,
                           AttnSpec.training(window=window))


def attention(p, x, *, qcfg: QuantConfig, n_heads: int, n_kv: int,
              d_head: int, positions, spec: AttnSpec,
              xkv: Optional[jax.Array] = None, kv_positions=None,
              rope_theta: float = 1e4, use_rope: bool = True) -> jax.Array:
    """Full attention layer (projections + mixing + output projection).

    ``spec`` carries the mask kind (causal/full/window), the query-position
    offset, and the chunk/tile geometry; cross-attention (``xkv``) should
    use a ``kind="full"`` spec.
    """
    from .layers import qdense
    cross = xkv is not None
    q, k, v = _project_qkv(p, x, xkv if cross else x, qcfg, n_heads, n_kv,
                           d_head, positions, kv_positions, rope_theta,
                           use_rope=use_rope and not cross)
    o = flash_attention(q, k, v, qcfg, spec)
    B, T = x.shape[:2]
    o = o.reshape(B, T, n_heads * d_head)
    return qdense(p["wo"], o, qcfg)


def decode_valid_mask(pos: jax.Array, S: int, window: int) -> jax.Array:
    """Per-row (B, S) cache-slot validity for one-token decode.

    Ring buffer (``window > 0``): slot ``s`` is valid if it was written
    within the last ``min(pos+1, window)`` steps.  Global cache: positions
    up to ``pos``.  Shared by the model decode path, the serve engine, and
    the kernel tests — the mask IS the ring semantics."""
    pos = jnp.asarray(pos, jnp.int32)
    kv_pos = jnp.arange(S)
    if window > 0:
        slot = pos % S
        age = (slot[:, None] - kv_pos[None, :]) % S
        return age <= jnp.minimum(pos, window - 1)[:, None]
    return kv_pos[None, :] <= pos[:, None]


def attention_decode(p, x, cache, *, qcfg: QuantConfig, n_heads: int,
                     n_kv: int, d_head: int, pos: jax.Array,
                     spec: AttnSpec, rope_theta: float = 1e4,
                     use_rope: bool = True):
    """One-token decode with a (k, v) ring/full cache.

    x: (B, 1, D); cache: {"k": (B, S, Hkv, d), "v": ...};
    pos: int32 scalar (whole batch at one position) or (B,) vector — the
    per-row form is what lets the continuous-batching scheduler advance
    slots that sit at different sequence lengths in one fixed-shape step.
    ``spec`` comes from :meth:`AttnSpec.decode`: ``kind="ring"`` layers use
    a ring buffer of size ``window``; ``kind="causal"`` a global cache.
    """
    B = x.shape[0]
    S = cache["k"].shape[1]
    window = spec.window if spec.kind == "ring" else 0
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, x, qcfg, n_heads, n_kv, d_head,
                                   positions, None, rope_theta,
                                   use_rope=use_rope)
    slot = pos % S if window > 0 else pos
    rows = jnp.arange(B)
    k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    G = n_heads // n_kv
    # Fold to the decode-kernel layout: q (B*Hkv, G, d), k/v (B*Hkv, S, d),
    # validity replicated per kv head.
    qf = q[:, 0].reshape(B * n_kv, G, d_head)
    kf = k.transpose(0, 2, 1, 3).reshape(B * n_kv, S, d_head)
    vf = v.transpose(0, 2, 1, 3).reshape(B * n_kv, S, v.shape[-1])
    valid = jnp.repeat(decode_valid_mask(pos, S, window), n_kv, axis=0)
    o = mx_contract(qf, (kf, vf), qcfg, kind="attn_decode", valid=valid)
    o = o.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    from .layers import qdense
    out = qdense(p["wo"], o, qcfg)
    return out, {"k": k, "v": v}


def paged_valid_mask(page_table: jax.Array, pos: jax.Array,
                     page_size: int) -> jax.Array:
    """(B, P*ps) per-view-position validity for paged decode: the position's
    page must be allocated AND the logical position must be <= pos (view
    position == logical position by construction).  Unallocated (-1) pages
    are clamped to page 0 by the gather and masked out here — including
    every position of a dead (freed) row, whose table is all -1."""
    B, P = page_table.shape
    vp = jnp.arange(P * page_size)
    allocated = (page_table >= 0)[:, vp // page_size]      # (B, P*ps)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    return allocated & (vp[None, :] <= pos[:, None])


def attention_decode_paged(p, x, cache, *, qcfg: QuantConfig, n_heads: int,
                           n_kv: int, d_head: int, pos: jax.Array,
                           page_table: jax.Array, spec: AttnSpec,
                           rope_theta: float = 1e4, use_rope: bool = True):
    """One-token decode against (k, v) page pools.

    x: (B, 1, D); cache: {"k": (N, Hkv, ps, d), "v": ...} — global pools
    shared by every row through the (B, P) ``page_table`` (physical page of
    logical page ``t // ps``; -1 = unallocated).  The new token scatters
    into its row's current tail page; dead rows (all -1 tables) resolve to
    an out-of-range sentinel and the write drops, so freed pages are never
    touched.  Scoring runs through ``mx_contract(kind="attn_decode_paged")``
    — a scalar-prefetch page-gather kernel on the fused path, the
    gather+slab oracle otherwise (bitwise-identical numerics).
    """
    B = x.shape[0]
    N, ps = cache["k"].shape[0], cache["k"].shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, x, qcfg, n_heads, n_kv, d_head,
                                   positions, None, rope_theta,
                                   use_rope=use_rope)
    rows = jnp.arange(B)
    phys = page_table[rows, pos // ps]
    # JAX scatter indices wrap when negative: dead rows must land out of
    # range (dropped), never at page -1 == page N-1.
    phys = jnp.where(phys < 0, N, phys)
    off = pos % ps
    k = cache["k"].at[phys, :, off].set(
        k_new[:, 0].astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[phys, :, off].set(
        v_new[:, 0].astype(cache["v"].dtype), mode="drop")
    G = n_heads // n_kv
    qf = q[:, 0].reshape(B * n_kv, G, d_head)
    valid = paged_valid_mask(page_table, pos, ps)
    o = mx_contract(qf, (k, v), qcfg, kind="attn_decode_paged", valid=valid,
                    pages=page_table)
    o = o.reshape(B, 1, n_heads * d_head).astype(x.dtype)
    from .layers import qdense
    out = qdense(p["wo"], o, qcfg)
    return out, {"k": k, "v": v}


def attention_prefill_chunk(p, x, prior_k, prior_v, *, qcfg: QuantConfig,
                            n_heads: int, n_kv: int, d_head: int, positions,
                            spec: AttnSpec, kv_mask=None,
                            rope_theta: float = 1e4, use_rope: bool = True):
    """One chunk of a continuous (chunked) prefill.

    x: (B, C, D) — the chunk's embeddings at absolute positions
    ``spec.q_offset .. q_offset + C - 1``; prior_k/prior_v:
    (B, q_offset, Hkv, d) — the already-written prefix K/V gathered from
    the page pools.  Computes the rectangular causal flash attention of the
    chunk's queries over prefix+chunk keys (PR 6's ``q_offset`` path) and
    returns (out (B, C, D), k_chunk, v_chunk) for the caller to write into
    fresh pages.  ``kv_mask`` ((B, C) bool) zeroes the K/V of padded tail
    positions *before* attention so pad garbage can neither be attended
    nor pollute at-rest MX block scales.
    """
    from .layers import qdense
    B, C = x.shape[:2]
    q, k, v = _project_qkv(p, x, x, qcfg, n_heads, n_kv, d_head, positions,
                           None, rope_theta, use_rope=use_rope)
    if kv_mask is not None:
        m = kv_mask[:, :, None, None]
        k = jnp.where(m, k, 0.0)
        v = jnp.where(m, v, 0.0)
    k_full = jnp.concatenate([prior_k.astype(k.dtype), k], axis=1)
    v_full = jnp.concatenate([prior_v.astype(v.dtype), v], axis=1)
    o = flash_attention(q, k_full, v_full, qcfg, spec)
    out = qdense(p["wo"], o.reshape(B, C, n_heads * d_head), qcfg)
    return out, k, v


def attention_prefill(p, x, *, qcfg: QuantConfig, n_heads: int, n_kv: int,
                      d_head: int, positions, spec: AttnSpec,
                      rope_theta: float = 1e4, use_rope: bool = True):
    """Fused prefill: full-sequence attention + the decode cache in one pass.

    Computes exactly what ``attention`` computes for the causal forward (so
    the single GEMM-heavy pass replaces T token steps), and additionally
    assembles the (k, v) cache that ``attention_decode`` expects: a
    zero-padded (B, cache_len, Hkv, d) buffer for global layers, or the
    ring buffer holding the last ``min(T, window)`` tokens at slots
    ``pos % ring`` for windowed layers.  Cache geometry comes from
    ``spec.cache_len`` / ``spec.window``.
    """
    from .layers import qdense
    B, T = x.shape[:2]
    window = spec.window if spec.kind == "window" else 0
    cache_len = spec.cache_len
    q, k, v = _project_qkv(p, x, x, qcfg, n_heads, n_kv, d_head, positions,
                           None, rope_theta, use_rope=use_rope)
    o = flash_attention(q, k, v, qcfg, spec)
    out = qdense(p["wo"], o.reshape(B, T, n_heads * d_head), qcfg)
    ring = min(cache_len, window) if window > 0 else cache_len
    if window > 0:
        m = min(T, ring)
        # The last m positions occupy distinct ring slots; older tokens
        # would have been overwritten during token-stepping anyway.
        slots = jnp.arange(T - m, T) % ring
        ck = jnp.zeros((B, ring) + k.shape[2:], k.dtype).at[:, slots].set(
            k[:, T - m:])
        cv = jnp.zeros((B, ring) + v.shape[2:], v.dtype).at[:, slots].set(
            v[:, T - m:])
    else:
        if T > cache_len:
            raise ValueError(f"prompt length {T} exceeds cache_len "
                             f"{cache_len}")
        pad = ((0, 0), (0, cache_len - T), (0, 0), (0, 0))
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, {"k": ck, "v": cv}
