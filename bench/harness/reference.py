"""The plain reference: a dense decoder's forward pass in straightforward
``jax.numpy`` at ``Precision.HIGHEST``.

It imports nothing of the program and reads only what the benchmark made:
the seeded weights of ``harness/weights.py`` and the seeded tokens.  It
follows the published architecture the configuration names: pre-norm
blocks, LayerNorm (or RMSNorm) with affine scale and bias, optional
RMSNorm on queries and keys, rotary embeddings on the two halves of each
head, grouped-query causal attention with 1/sqrt(d_head) scaling, a GELU
(tanh form) MLP, biases where the weights have them, a final norm and a
head of its own or tied to the embedding table.  Layers run one at a time
and attention in query chunks, so the reference fits beside the weights
at the timed sizes.

By default it computes in float32.  A mix states the precision its cell
runs in under ``reference`` (see :class:`Precision`), and the reference
then computes in that precision, written out here from the OCP MX
definition: weight-only MX rounds the weight operand of every projection
in blocks of 32 along its input width, and activations are held in
bfloat16 between the operations.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
EPS = 1e-5
BLOCK = 32

# OCP MX element formats: (mantissa bits, least normal exponent, largest
# exponent, largest value).  Elements round to nearest even on the grid
# and saturate; a block's shared scale is 2^(floor(log2 amax) - emax).
FORMATS = {"e4m3": (3, -6, 8, 448.0), "e2m1": (1, 0, 2, 6.0)}


@dataclasses.dataclass(frozen=True)
class Precision:
    """What the reference computes in.  ``weights``: an MX element format
    (a key of ``FORMATS``) for the weight operand of each projection, or
    None for none (weight-only MX, blocks along the input width); ``act``:
    "float32", or "bfloat16" to hold activations in bfloat16 between
    operations."""
    weights: Optional[str] = None
    act: str = "float32"

    @staticmethod
    def of(d: Optional[Dict[str, Any]]) -> "Precision":
        return Precision(**(d or {}))


FP32 = Precision()


def _floor_log2(a):
    """floor(log2 a) for a > 0, exactly (from the float's exponent)."""
    return jnp.frexp(a)[1] - 1


def mx_round(x, axis: int, fmt: str):
    """Round ``x`` to the MX format ``fmt``: blocks of 32 along ``axis``
    share a power-of-two scale 2^(floor(log2 amax) - emax), elements round
    to nearest even on the format's grid and saturate."""
    mant, emin, emax, top = FORMATS[fmt]
    dtype = x.dtype
    x = jnp.moveaxis(x.astype(F32), axis, -1)
    shp = x.shape
    if shp[-1] % BLOCK:
        raise ValueError(f"axis of {shp[-1]} is not in blocks of {BLOCK}")
    xb = x.reshape(shp[:-1] + (shp[-1] // BLOCK, BLOCK))
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    e = jnp.clip(_floor_log2(jnp.where(amax > 0, amax, 1.0)) - emax,
                 -126, 127)
    scale = jnp.exp2(e.astype(F32))
    y = xb / scale
    ey = jnp.maximum(_floor_log2(jnp.where(y != 0, jnp.abs(y), 1.0)), emin)
    quantum = jnp.exp2((ey - mant).astype(F32))
    y = jnp.clip(jnp.round(y / quantum) * quantum, -top, top)
    return jnp.moveaxis((y * scale).reshape(shp), -1, axis).astype(dtype)


def _act(x, pr: Precision):
    """Hold ``x`` in bfloat16."""
    if pr.act == "float32":
        return x
    return x.astype(jnp.bfloat16).astype(F32)


def _mm(x, w):
    return jnp.einsum("...k,kn->...n", x, w.astype(F32), precision=HI)


def _dense(p, x, pr: Precision):
    """``x @ w (+ b)``."""
    w = _act(p["w"].astype(F32), pr)
    if pr.weights:
        w = mx_round(w, 0, pr.weights)
    y = _act(_mm(x, w), pr)
    if "b" in p:
        y = _act(y + _act(p["b"].astype(F32), pr), pr)
    return y


def _norm(p, x, layernorm: bool, pr: Precision = FP32):
    x = x.astype(F32)
    if layernorm:
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    scale = p["scale"].astype(F32)
    y = x * scale
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    return _act(y, pr)


def _rope(x, pos, theta: float):
    """x: (B, T, H, d); pos: (T,)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs                      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _chunk(T: int, C: int) -> int:
    C = min(C, T)
    while T % C:
        C -= 1
    return C


def _attend(q, k, v, q_chunk: int):
    """Causal attention in float32; q (B, T, H, d), k/v (B, T, H, d)."""
    B, T, H, d = q.shape
    C = _chunk(T, q_chunk)
    scale = 1.0 / math.sqrt(d)
    kpos = jnp.arange(T)

    def one(args):
        qc, start = args                                       # (B, C, H, d)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k, precision=HI) * scale
        qpos = start + jnp.arange(C)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    qs = q.reshape(B, T // C, C, H, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (qs, jnp.arange(0, T, C)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, d)


def _layer(x, p, m: Dict[str, Any], pos, q_chunk: int, pr: Precision):
    B, T, _ = x.shape
    H, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    ln = m["norm"] == "layernorm"
    a = p["attn"]

    def proj(name, h, n):
        return _dense(a[name], h, pr).reshape(B, T, n, dh)

    h = _norm(p["ln1"], x, ln, pr)
    q, k, v = proj("wq", h, H), proj("wk", h, Hkv), proj("wv", h, Hkv)
    if "q_norm" in a:
        q = _norm(a["q_norm"], q, False, pr)
        k = _norm(a["k_norm"], k, False, pr)
    q = _act(_rope(q, pos, m["rope_theta"]), pr)
    k = _act(_rope(k, pos, m["rope_theta"]), pr)
    G = H // Hkv
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    o = _attend(q, k, v, q_chunk)
    o = _act(o, pr).reshape(B, T, H * dh)
    x = _act(x + _dense(a["wo"], o, pr), pr)
    h = _norm(p["ln2"], x, ln, pr)
    mp = p["mlp"]
    up = _dense(mp["w_up"], h, pr)
    if m["act"] == "gelu":
        act = jax.nn.gelu(up, approximate=True)
    elif m["act"] == "swiglu":
        act = jax.nn.silu(_dense(mp["w_gate"], h, pr)) * up
    else:
        raise ValueError(f"reference has no activation {m['act']!r}")
    return _act(x + _dense(mp["w_down"], _act(act, pr), pr), pr)


def hidden(params, tokens, m: Dict[str, Any], q_chunk: int = 512,
           pr: Precision = FP32):
    """Final-norm hidden states (B, T, D)."""
    T = tokens.shape[1]
    pos = jnp.arange(T)
    x = _act(params["embed"]["table"].astype(F32)[tokens], pr)

    def body(x, p):
        return _layer(x, p, m, pos, q_chunk, pr), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0]["b0"])
    return _norm(params["final_ln"], x, m["norm"] == "layernorm", pr)


def head(params) -> Dict[str, Any]:
    """The (D, vocab) output projection: the head, or the tied table."""
    if "lm_head" in params:
        return params["lm_head"]
    return {"w": params["embed"]["table"].T}


def logits(params, h, pr: Precision = FP32):
    return _dense(head(params), h, pr)
