"""The plain reference of a DeepSeek-V3-style decoder, one chip's share of
an expert-parallel layer: its forward pass in straightforward
``jax.numpy`` at ``Precision.HIGHEST``.

It imports nothing of the program and reads only what the benchmark made:
the seeded weights of ``harness/weights_deepseek_v3.py`` and the seeded
tokens.  It follows the published layer equations (``modeling_deepseek.py``
of ``model_type`` deepseek_v3, without rope scaling or multi-token
prediction):

* pre-norm blocks, RMSNorm with eps ``EPS``; the kv latent's RMSNorm with
  ``LATENT_EPS``;
* Multi-head Latent Attention in its expanded form: queries by one
  projection ``w_q`` (no q_lora) split into nope and rope parts; the
  latent ``ckv = norm(x @ w_dkv)``, a single rope key ``k_r = rope(x @
  w_kr)`` shared by every head; per head ``k = [ckv @ w_uk, k_r]``, ``v =
  ckv @ w_uv``; causal softmax attention scaled by 1/sqrt(nope + rope);
  rotary embeddings on the two halves of the rope dims;
* the first ``first_dense`` layers with a SwiGLU MLP, the rest with
  experts: the router's float32 logits over all ``n_experts``, sigmoid
  scores, the top-k chosen on scores plus the correction bias, gates the
  chosen unbiased scores normalized over the k and multiplied by
  ``routed_scale``; the experts this chip holds (``first_expert ..
  + experts_held``) are all evaluated densely on every token and weighed
  by their gate (zero where not chosen), so what the absent experts would
  add is left out, as the program leaves it out; plus the shared experts,
  one SwiGLU MLP of width ``n_shared * moe_dff``;
* a final norm and an untied head.

No kernels, cache or batching: layers run one at a time, attention in
query chunks and the experts one at a time, so the reference fits beside
the weights at the timed sizes.  By default it computes in float32; a mix
states the precision its cell runs in under ``reference``
(``reference.Precision``), and then every projection weight is MX-rounded
in blocks of 32 along its input width and activations are held in
bfloat16 between operations.  The router and its bias are not rounded.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .reference import F32, FP32, HI, Precision, _act, _chunk, _dense, _rope

EPS = 1e-5
LATENT_EPS = 1e-6


def _rms(p, x, eps: float, pr: Precision):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _act(x * p["scale"].astype(F32), pr)


def _attend(q, k, v, q_chunk: int):
    """Causal attention in float32: q, k (B, T, H, dqk), v (B, T, H, dv)."""
    B, T, H, d = q.shape
    dv = v.shape[-1]
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
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, dv)


def mla(a, h, m: Dict[str, Any], pos, q_chunk: int, pr: Precision):
    """Expanded-form latent attention of normed ``h`` (B, T, D)."""
    B, T, _ = h.shape
    H, nope, rope = m["n_heads"], m["nope_dim"], m["rope_dim"]
    q = _dense(a["w_q"], h, pr).reshape(B, T, H, nope + rope)
    q_nope = q[..., :nope]
    q_rope = _act(_rope(q[..., nope:], pos, m["rope_theta"]), pr)
    ckv = _rms(a["kv_ln"], _dense(a["w_dkv"], h, pr), LATENT_EPS, pr)
    kr = _dense(a["w_kr"], h, pr).reshape(B, T, 1, rope)
    kr = _act(_rope(kr, pos, m["rope_theta"]), pr)
    k_nope = _dense(a["w_uk"], ckv, pr).reshape(B, T, H, nope)
    v = _dense(a["w_uv"], ckv, pr).reshape(B, T, H, m["v_head"])
    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (B, T, H, rope))], -1)
    o = _act(_attend(qf, kf, v, q_chunk), pr).reshape(B, T, -1)
    return _dense(a["wo"], o, pr)


def swiglu(p, h, pr: Precision):
    """``w_down(silu(h @ w_gate) * (h @ w_up))``; ``p`` holds the three
    weights as arrays."""
    up = _dense({"w": p["w_up"]}, h, pr)
    g = _dense({"w": p["w_gate"]}, h, pr)
    return _dense({"w": p["w_down"]}, _act(jax.nn.silu(g) * up, pr), pr)


def gates(p, h, m: Dict[str, Any]):
    """(gates (..., E) float32, zero but at the chosen experts; the
    chosen expert ids (..., k)): the DeepSeek-V3 router on ``h``."""
    logits = jnp.einsum("...d,de->...e", h.astype(F32),
                        p["router"].astype(F32), precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(F32), m["top_k"])
    g = jnp.take_along_axis(scores, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * m["routed_scale"]
    onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=F32)
    return jnp.sum(onehot * g[..., None], axis=-2), idx


def tie_margin(p, h, m: Dict[str, Any]):
    """How near the router's choice on ``h`` sits to a tie that changes
    this chip's part: the gap between the last chosen and the first
    unchosen biased score where either expert is held here, else +inf."""
    logits = jnp.einsum("...d,de->...e", h.astype(F32),
                        p["router"].astype(F32), precision=HI)
    choice = jax.nn.sigmoid(logits) + p["router_bias"].astype(F32)
    k = m["top_k"]
    top, idx = jax.lax.top_k(choice, k + 1)
    first = m.get("first_expert", 0)
    here = (idx[..., k - 1:] >= first) & (
        idx[..., k - 1:] < first + p["w_up"].shape[0])
    return jnp.where(here.any(-1), top[..., k - 1] - top[..., k], jnp.inf)


def held_experts(p, h, m: Dict[str, Any], pr: Precision):
    """The held experts' part of the routed sum for normed ``h``."""
    g, _ = gates(p, h, m)
    first = m.get("first_expert", 0)
    held = p["w_up"].shape[0]
    g = g[..., first:first + held]

    def one(acc, xs):
        w, gj = xs
        return acc + gj[..., None] * swiglu(w, h, pr), None

    w = {k: p[k] for k in ("w_up", "w_gate", "w_down")}
    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32),
                        (w, jnp.moveaxis(g, -1, 0)))
    return _act(y, pr)


def _flat(mlp):
    return {k: v["w"] for k, v in mlp.items()}


def _layer(x, p, m: Dict[str, Any], pos, q_chunk: int, pr: Precision):
    """(x after the layer, the layer's ``tie_margin`` or +inf)."""
    x = _act(x + mla(p["attn"], _rms(p["ln1"], x, EPS, pr), m, pos, q_chunk,
                     pr), pr)
    h = _rms(p["ln2"], x, EPS, pr)
    if "moe" in p:
        y = _act(held_experts(p["moe"], h, m, pr)
                 + swiglu(_flat(p["shared"]), h, pr), pr)
        margin = tie_margin(p["moe"], h, m)
    else:
        y = swiglu(_flat(p["mlp"]), h, pr)
        margin = jnp.full(x.shape[:-1], jnp.inf, F32)
    return _act(x + y, pr), margin


def hidden_and_margins(params, tokens, m: Dict[str, Any],
                       q_chunk: int = 512, pr: Precision = FP32):
    """Final-norm hidden states (B, T, D), and each position's least
    ``tie_margin`` over the layers (B, T)."""
    pos = jnp.arange(tokens.shape[1])
    x = _act(params["embed"]["table"].astype(F32)[tokens], pr)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for group in params["blocks"]:
        def body(carry, p):
            x, margin = carry
            x, mg = _layer(x, p, m, pos, q_chunk, pr)
            return (x, jnp.minimum(margin, mg)), None

        (x, margin), _ = jax.lax.scan(body, (x, margin), group["b0"])
    return _rms(params["final_ln"], x, EPS, pr), margin


def hidden(params, tokens, m: Dict[str, Any], q_chunk: int = 512,
           pr: Precision = FP32):
    """Final-norm hidden states (B, T, D)."""
    return hidden_and_margins(params, tokens, m, q_chunk, pr)[0]


def logits(params, h, pr: Precision = FP32):
    return _dense(params["lm_head"], h, pr)
