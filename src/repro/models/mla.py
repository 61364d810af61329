"""Multi-head Latent Attention (DeepSeek-V2/V3) with MX-quantized projections.

Training and prefill use the expanded form (per-head K/V decompressed,
chunked flash attention); decoding uses the absorbed form operating
directly on the compressed latent cache (kv_lora + rope dims per
position) — the whole point of MLA.  All up/down projections are MX
GEMMs; the latent cache is stored bf16 (the paper quantizes GEMM
operands, not state).  Queries come through a low-rank ``w_dq`` /
``w_uq`` pair with a norm between (``q_lora > 0``, DeepSeek-V2) or one
``w_q`` projection (``q_lora`` 0, as Moonlight and DeepSeek-V2-Lite
publish it).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import AttnSpec, QuantConfig, mx_contract, quantize_mx
from .layers import dense_init, norm_init, apply_norm, qdense, rope
from .attention import (flash_attention, paged_valid_mask, _maybe_quant,
                        NEG_INF)

__all__ = ["mla_init", "mla_apply", "mla_decode", "mla_decode_paged",
           "mla_prefill", "mla_prefill_chunk"]

# The latent norms' epsilon: the published DeepSeek code builds them with
# its RMSNorm's default, apart from the blocks' ``rms_norm_eps``.
LATENT_EPS = 1e-6


def mla_init(key, d_model: int, n_heads: int, q_lora: int, kv_lora: int,
             nope: int, rope_dim: int, v_head: int, n_layers: int = 1):
    ks = jax.random.split(key, 8)
    if q_lora:
        q = {"w_dq": dense_init(ks[0], d_model, q_lora),
             "q_ln": norm_init(q_lora),
             "w_uq": dense_init(ks[1], q_lora, n_heads * (nope + rope_dim))}
    else:
        q = {"w_q": dense_init(ks[0], d_model, n_heads * (nope + rope_dim))}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d_model, kv_lora),
        "kv_ln": norm_init(kv_lora),
        "w_uk": dense_init(ks[3], kv_lora, n_heads * nope),
        "w_uv": dense_init(ks[4], kv_lora, n_heads * v_head),
        "w_kr": dense_init(ks[5], d_model, rope_dim),
        "wo": dense_init(ks[6], n_heads * v_head, d_model,
                         std=1.0 / math.sqrt(n_heads * v_head * 2 * n_layers)),
    }


def _latents(p, x, qcfg, positions, rope_theta):
    """Queries (B, T, H*(nope+rope), before rope) and the (ckv, k_rope)
    latent pair."""
    B, T, _ = x.shape
    if "w_q" in p:
        q = qdense(p["w_q"], x, qcfg)
    else:
        q = qdense(p["w_uq"], apply_norm(p["q_ln"], qdense(p["w_dq"], x, qcfg),
                                         qcfg, eps=LATENT_EPS), qcfg)
    ckv = apply_norm(p["kv_ln"], qdense(p["w_dkv"], x, qcfg), qcfg,
                     eps=LATENT_EPS)
    kr = qdense(p["w_kr"], x, qcfg).reshape(B, T, 1, -1)
    kr = rope(kr, positions, rope_theta).reshape(B, T, -1)
    return q, ckv, kr


def _expanded_attend(p, q, ckv, kr, qcfg, n_heads, nope, rope_dim, v_head,
                     positions, rope_theta, spec):
    """Expanded-form attention of queries ``q`` (B, T, H*(nope+rope)) at
    ``positions`` over the latents ``ckv``/``kr`` (B, S, ·) of positions
    0..S-1, decompressed per head; ``spec`` places the queries."""
    B, T, _ = q.shape
    S = ckv.shape[1]
    q = q.reshape(B, T, n_heads, nope + rope_dim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, rope_theta)
    k_nope = qdense(p["w_uk"], ckv, qcfg).reshape(B, S, n_heads, nope)
    v = qdense(p["w_uv"], ckv, qcfg).reshape(B, S, n_heads, v_head)
    k_rope = jnp.broadcast_to(kr[:, :, None, :], (B, S, n_heads, rope_dim))
    # Layout for flash: every head is its own "kv head" (group G=1).
    qf = jnp.concatenate([q_nope, q_rope], -1)[:, :, :, None, :]  # (B,T,H,1,dqk)
    kf = jnp.concatenate([k_nope, k_rope], -1)      # (B, S, H, dqk)
    o = flash_attention(qf, kf, v, qcfg, spec)
    o = o.reshape(B, T, n_heads * v_head)
    return qdense(p["wo"], o, qcfg)


def _forward(p, x, qcfg, n_heads, nope, rope_dim, v_head, positions,
             rope_theta, spec):
    """Full-sequence expanded-form attention; also returns the latents."""
    q, ckv, kr = _latents(p, x, qcfg, positions, rope_theta)
    out = _expanded_attend(p, q, ckv, kr, qcfg, n_heads, nope, rope_dim,
                           v_head, positions, rope_theta, spec)
    return out, ckv, kr


def mla_apply(p, x, *, qcfg: QuantConfig, n_heads: int, nope: int,
              rope_dim: int, v_head: int, positions, spec: AttnSpec,
              rope_theta: float = 1e4) -> jax.Array:
    return _forward(p, x, qcfg, n_heads, nope, rope_dim, v_head, positions,
                    rope_theta, spec)[0]


def mla_prefill(p, x, *, qcfg: QuantConfig, n_heads: int, nope: int,
                rope_dim: int, v_head: int, positions, spec: AttnSpec,
                rope_theta: float = 1e4) -> Tuple[jax.Array, dict]:
    """Fused prefill: expanded-form attention + the compressed latent cache
    (what ``mla_decode`` consumes) in one pass.  Scores here use the
    expanded form while decode uses the absorbed form — same math up to
    fp associativity, so parity is tight-tolerance rather than bitwise."""
    B, T, _ = x.shape
    cache_len = spec.cache_len
    if T > cache_len:
        raise ValueError(f"prompt length {T} exceeds cache_len {cache_len}")
    out, ckv, kr = _forward(p, x, qcfg, n_heads, nope, rope_dim, v_head,
                            positions, rope_theta, spec)
    pad = ((0, 0), (0, cache_len - T), (0, 0))
    return out, {"ckv": jnp.pad(ckv, pad), "kr": jnp.pad(kr, pad)}


def mla_prefill_chunk(p, x, prior_ckv, prior_kr, *, qcfg: QuantConfig,
                      n_heads: int, nope: int, rope_dim: int, v_head: int,
                      positions, spec: AttnSpec, kv_mask=None,
                      rope_theta: float = 1e4):
    """One chunk of a continuous (chunked) prefill on latent pages.

    x: (B, C, D) at absolute positions ``spec.q_offset ..``;
    prior_ckv/prior_kr: (B, q_offset, ·) — the prefix latents gathered
    from the page pools.  The chunk's queries attend, in the expanded
    form, the prefix and chunk latents decompressed together (what
    ``mla_prefill`` computes for these rows); returns (out (B, C, D),
    ckv_chunk, kr_chunk) for the caller to write into fresh pages.
    ``kv_mask`` ((B, C) bool) zeroes the latents of padded tail
    positions."""
    q, ckv, kr = _latents(p, x, qcfg, positions, rope_theta)
    if kv_mask is not None:
        ckv = jnp.where(kv_mask[..., None], ckv, 0.0).astype(ckv.dtype)
        kr = jnp.where(kv_mask[..., None], kr, 0.0).astype(kr.dtype)
    ckv_all = jnp.concatenate([prior_ckv.astype(ckv.dtype), ckv], axis=1)
    kr_all = jnp.concatenate([prior_kr.astype(kr.dtype), kr], axis=1)
    out = _expanded_attend(p, q, ckv_all, kr_all, qcfg, n_heads, nope,
                           rope_dim, v_head, positions, rope_theta, spec)
    return out, ckv, kr


def mla_decode(p, x, cache, *, qcfg: QuantConfig, n_heads: int, nope: int,
               rope_dim: int, v_head: int, pos, rope_theta: float = 1e4
               ) -> Tuple[jax.Array, dict]:
    """Absorbed-form decode on the compressed cache.

    cache: {"ckv": (B, S, kv_lora), "kr": (B, S, rope_dim)}; x: (B, 1, D);
    pos: int32 scalar or (B,) per-row positions (continuous batching).
    Scores: q_nopeᵀ·W_uk·ckv + q_ropeᵀ·k_rope; context is accumulated in
    latent space then decompressed through W_uv once per step.
    """
    B = x.shape[0]
    S = cache["ckv"].shape[1]
    kv_lora = cache["ckv"].shape[-1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, ckv_new, kr_new = _latents(p, x, qcfg, positions, rope_theta)
    rows = jnp.arange(B)
    ckv = cache["ckv"].at[rows, pos].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    kr = cache["kr"].at[rows, pos].set(kr_new[:, 0].astype(cache["kr"].dtype))

    out = _absorbed_attend(p, x, q, ckv, kr, qcfg, n_heads, nope, rope_dim,
                           v_head, positions, rope_theta,
                           jnp.arange(S)[None, :] <= pos[:, None])
    return out, {"ckv": ckv, "kr": kr}


def _absorbed_weight(p, qcfg: QuantConfig, dtype):
    """A (kv_lora, ·) up-projection as its decompressing GEMM ``qdense``
    sees it: in ``dtype``, MX-rounded in blocks along kv_lora where the
    preset quantizes weights, so the absorbed decode and the expanded
    prefill use the same W_uk / W_uv."""
    w = p["w"].astype(dtype)
    return quantize_mx(w, qcfg.w_fwd, axis=0, block=qcfg.block,
                       scale_mode=qcfg.scale_mode)


def _absorbed_attend(p, x, q, ckv, kr, qcfg, n_heads, nope, rope_dim,
                     v_head, positions, rope_theta, valid):
    """Absorbed-form scoring + context over a contiguous (B, S, ·) latent
    view with a precomputed (B, S) validity mask — shared verbatim by the
    slab and paged decode paths so gathering pages cannot drift from the
    slab numerics."""
    B = x.shape[0]
    kv_lora = ckv.shape[-1]
    q = q.reshape(B, n_heads, nope + rope_dim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope[:, None], positions, rope_theta)[:, 0]
    w_uk = _absorbed_weight(p["w_uk"], qcfg, x.dtype).reshape(
        kv_lora, n_heads, nope)
    # Absorb W_uk into the query: q_eff (B, H, kv_lora).
    q_eff = jnp.einsum("bhd,chd->bhc", _maybe_quant(q_nope, qcfg, -1),
                       w_uk, preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(nope + rope_dim)
    s = (jnp.einsum("bhc,bsc->bhs", q_eff.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    # The latent-space context product is a standard P·V contraction:
    # route it through the shared dispatcher (pr quantized along the cache
    # axis per row, ckv along the cache axis per column when qcfg.attn).
    ctx = mx_contract(pr, ckv.astype(jnp.float32), qcfg, kind="attn_pv")
    w_uv = _absorbed_weight(p["w_uv"], qcfg, x.dtype).reshape(
        kv_lora, n_heads, v_head)
    o = jnp.einsum("bhc,chv->bhv", ctx.astype(x.dtype), w_uv)
    o = o.reshape(B, 1, n_heads * v_head)
    return qdense(p["wo"], o, qcfg)


def mla_decode_paged(p, x, cache, *, qcfg: QuantConfig, n_heads: int,
                     nope: int, rope_dim: int, v_head: int, pos,
                     page_table, page_size: int, rope_theta: float = 1e4
                     ) -> Tuple[jax.Array, dict]:
    """Absorbed-form decode on paged latent pools.

    cache: {"ckv": (N, ps, kv_lora), "kr": (N, ps, rope_dim)} — global page
    pools addressed through the (B, P) ``page_table``.  Latents stay bf16
    at rest (the paper quantizes GEMM operands, not state); the paging
    transform is a pure scatter+gather, so decode is bitwise equal to the
    slab path on the same logical contents.  Dead rows (all -1 tables)
    scatter to an out-of-range sentinel and drop."""
    B = x.shape[0]
    N, ps = cache["ckv"].shape[0], cache["ckv"].shape[1]
    P = page_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q, ckv_new, kr_new = _latents(p, x, qcfg, positions, rope_theta)
    rows = jnp.arange(B)
    phys = page_table[rows, pos // ps]
    phys = jnp.where(phys < 0, N, phys)      # negatives wrap: drop instead
    off = pos % ps
    ckv_pool = cache["ckv"].at[phys, off].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype), mode="drop")
    kr_pool = cache["kr"].at[phys, off].set(
        kr_new[:, 0].astype(cache["kr"].dtype), mode="drop")
    ptc = jnp.clip(page_table, 0, N - 1)
    ckv = ckv_pool[ptc].reshape(B, P * ps, -1)
    kr = kr_pool[ptc].reshape(B, P * ps, -1)
    valid = paged_valid_mask(page_table, pos, ps)
    out = _absorbed_attend(p, x, q, ckv, kr, qcfg, n_heads, nope, rope_dim,
                           v_head, positions, rope_theta, valid)
    return out, {"ckv": ckv_pool, "kr": kr_pool}
