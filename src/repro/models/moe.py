"""Mixture-of-Experts: top-k routing with capacity-based sort dispatch.

Two routers, both kept fp32 (routing is famously precision-sensitive and
the paper quantizes only GEMM operands; the router GEMM runs at full fp32
precision on every backend):

  * "softmax" (DeepSeek-V2 and most MoE LMs): softmax scores, top-k,
    gates renormalized over the k;
  * "sigmoid" (DeepSeek-V3 ``noaux_tc``): sigmoid scores; the top-k are
    chosen on the scores plus a per-expert correction bias
    (``router_bias``, used for selection only); gates are the unbiased
    scores of the chosen experts, normalized over the k.

Either router's gates are then multiplied by ``routed_scale``
(``routed_scaling_factor``).  Shared experts live beside this layer
(``transformer.py``).

Expert parallelism: the layer holds experts ``[first_expert, first_expert
+ held)`` of the router's E (its ``w_*`` tensors stack ``held`` experts),
routes every token over all E, and computes only its own experts' part of
``sum_k gate_k * expert_k(x)``; the exchange with the chips holding the
others is the caller's.  Dispatch is a stable argsort of the assignments
into an (held, capacity) buffer (tokens over capacity are dropped and
counted); per-expert GEMMs go through the MX-quantized batched matmul so
the paper's technique covers expert weights exactly like dense ones.
Training sizes the capacity by ``capacity_factor``; serving is dropless:
the capacity is the step's token count, which no expert can exceed since a
token picks an expert at most once.

Expert tensors are stacked (E, D, F): under the production mesh the E axis
shards on "model" (expert parallelism); the scatter/gather dispatch
lowers to all-to-alls under GSPMD.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, mx_contract
from repro.parallel.sharding import shard_spec
from .layers import trunc_normal
from .mlp import ACTIVATIONS

__all__ = ["moe_init", "moe_apply", "route"]

ROUTERS = ("softmax", "sigmoid")


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             act: str = "swiglu", n_layers: int = 1, *, held: int = 0,
             router: str = "softmax"):
    """Router over ``n_experts``; expert tensors for ``held`` of them (all
    when 0).  The sigmoid router adds its selection bias, zero at init."""
    if router not in ROUTERS:
        raise ValueError(f"unknown router {router!r}; know {ROUTERS}")
    gated = act in ("swiglu", "geglu")
    n_held = held or n_experts
    ks = jax.random.split(key, 4)
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff * 2 * n_layers)
    p = {
        "router": trunc_normal(ks[0], (d_model, n_experts), std_in),
        "w_up": trunc_normal(ks[1], (n_held, d_model, d_ff), std_in),
        "w_down": trunc_normal(ks[2], (n_held, d_ff, d_model), std_out),
    }
    if gated:
        p["w_gate"] = trunc_normal(ks[3], (n_held, d_model, d_ff), std_in)
    if router == "sigmoid":
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    return p


def _capacity(T: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(factor * T * top_k / n_experts)
    return max(32, (c + 31) // 32 * 32)     # MX-block / lane aligned


def route(p, x: jax.Array, *, top_k: int, router: str = "softmax",
          routed_scale: float = 1.0
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(gates (T, k) fp32, expert ids (T, k) int32, scores (T, E) fp32) of
    flat tokens ``x`` (T, D) over the router's E experts."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(scores, top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * routed_scale, idx, scores


def moe_apply(p, x: jax.Array, qcfg: QuantConfig, *, top_k: int,
              act: str = "swiglu", capacity_factor: float = 1.25,
              router: str = "softmax", routed_scale: float = 1.0,
              first_expert: int = 0, dropless: bool = False,
              token_mask: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, dict]:
    """x: (T, D) flat tokens -> (y, metrics).  ``y`` is this layer's held
    experts' part of the routed sum.  ``token_mask`` ((T,) bool) leaves
    tokens out of the routing altogether (padding, dead rows).  Metrics:
    the paper-style load-balance aux loss, the dropped share of the
    assignments to held experts, and two counters: ``held_tokens`` ((held,)
    int32, tokens routed to each held expert) and ``dropped`` (int32,
    those assignments over capacity)."""
    T, D = x.shape
    E = p["router"].shape[-1]
    H = p["w_up"].shape[0]
    C = T if dropless else _capacity(T, top_k, E, capacity_factor)

    gates, idx, scores = route(p, x, top_k=top_k, router=router,
                               routed_scale=routed_scale)
    local = idx - first_expert
    here = (local >= 0) & (local < H)
    if token_mask is not None:
        here = here & token_mask[:, None]
    # Assignments to experts held elsewhere (or masked) sort last, into a
    # bucket H that no slot reads.
    flat_e = jnp.where(here, local, H).reshape(-1)        # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    token_of = order // top_k
    counts = jnp.bincount(flat_e, length=H + 1)
    offsets = jnp.cumsum(counts) - counts                 # exclusive

    # GATHER-ONLY dispatch (no scatters): slot (e, c) of the expert buffer
    # is filled by sorted assignment offsets[e]+c, so the buffer is a pure
    # gather; the combine inverts the sort permutation (another gather)
    # and reduces over the k assignments with a reshape-sum.  GSPMD
    # partitions global scatters poorly (measured 3-8x collective blowups
    # for scatter-based dispatch under every layout we tried — §Perf log);
    # gathers partition cleanly.
    a_of_slot = offsets[:H, None] + jnp.arange(C)[None, :]      # (H, C)
    valid = jnp.arange(C)[None, :] < counts[:H, None]
    a_of_slot = jnp.clip(a_of_slot, 0, T * top_k - 1)
    tok_of_slot = token_of[a_of_slot]                           # (H, C)
    h_in = x[tok_of_slot] * valid[..., None].astype(x.dtype)    # (H, C, D)
    # E-sharded only: 2-D (E, capacity) sharding re-introduced 4+ TB of
    # all-gathers under GSPMD (refuted; §Perf iteration log)
    h_in = shard_spec(h_in, ("model", None, None))

    up = mx_contract(h_in, p["w_up"].astype(x.dtype), qcfg, kind="bmm")
    if "w_gate" in p:
        g = mx_contract(h_in, p["w_gate"].astype(x.dtype), qcfg, kind="bmm")
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * up
    else:
        h = ACTIVATIONS[act](up)
    out = mx_contract(h, p["w_down"].astype(x.dtype), qcfg,
                      kind="bmm")                               # (H, C, D)
    out = out * valid[..., None].astype(out.dtype)

    # combine: assignment a sits at flat slot sorted_pos[a] in the (H*C)
    # buffer iff it is held here and its within-expert position fits the
    # capacity.
    pos = jnp.arange(T * top_k) - offsets[flat_e[order]]
    inv_order = jnp.argsort(order, stable=True)                 # a -> rank
    pos_a = pos[inv_order]
    held_a = flat_e < H
    kept_a = held_a & (pos_a < C)
    flat_slot = jnp.clip(flat_e * C + pos_a, 0, H * C - 1)
    y_assign = out.reshape(H * C, D)[flat_slot] \
        * kept_a[:, None].astype(out.dtype)                     # (T*k, D)
    # gates stay fp32 through the weighted sum
    y = jnp.sum(y_assign.reshape(T, top_k, D).astype(jnp.float32)
                * gates.reshape(T, top_k, 1), axis=1).astype(x.dtype)
    y = shard_spec(y, ("batch", None))

    # load balance over the router's experts, held here or not
    frac = jnp.bincount(idx.reshape(-1), length=E) / max(T * top_k, 1)
    dropped = jnp.sum(held_a & ~kept_a).astype(jnp.int32)
    metrics = {
        "aux_loss": E * jnp.sum(frac * scores.mean(0)),   # load-balance loss
        "dropped_frac": dropped / jnp.maximum(jnp.sum(held_a), 1),
        "held_tokens": counts[:H].astype(jnp.int32),
        "dropped": dropped,
    }
    return y, metrics
