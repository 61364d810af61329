"""Weights of a DeepSeek-V3-style decoder (MLA attention, a leading dense
layer, then layers of sigmoid-routed experts beside shared experts) from
``--seed``, made on the device in one jitted call.

The tree has the layout the program's ``lm_init`` gives such a config: a
scan group ``blocks[0]["b0"]`` stacking the ``first_dense`` dense layers
and ``blocks[1]["b0"]`` stacking the expert layers, each holding the
``experts_held`` experts this chip serves and the router over all
``n_experts``.  Queries come by one ``w_q`` projection (``q_lora`` 0).
Norm scales are drawn near 1 rather than left there, so that a path that
drops one shows in the comparison; the router's selection-only correction
bias is drawn N(0, ``BIAS_STD``^2) in float32, large enough to move over
40% of the top-6 choices among 64 experts.  The plain reference
(``harness/reference_deepseek_v3.py``) reads the same tree.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import _dense, _norm, _tn, key

BIAS_STD = 0.1


def _mla(k, L, m, dtype) -> Dict[str, Any]:
    D, H = m["d_model"], m["n_heads"]
    nope, rope, v, kv = m["nope_dim"], m["rope_dim"], m["v_head"], m["kv_lora"]
    if m.get("q_lora"):
        raise ValueError("this maker covers queries by one projection "
                         "(q_lora 0)")
    ks = iter(jax.random.split(k, 8))
    out_std = 1 / math.sqrt(2 * m["n_layers"] * H * v)
    return {"w_q": _dense(next(ks), (L, D, H * (nope + rope)),
                          1 / math.sqrt(D), False, dtype),
            "w_dkv": _dense(next(ks), (L, D, kv), 1 / math.sqrt(D), False,
                            dtype),
            "kv_ln": _norm(next(ks), (L, kv), False, dtype),
            "w_uk": _dense(next(ks), (L, kv, H * nope), 1 / math.sqrt(kv),
                           False, dtype),
            "w_uv": _dense(next(ks), (L, kv, H * v), 1 / math.sqrt(kv),
                           False, dtype),
            "w_kr": _dense(next(ks), (L, D, rope), 1 / math.sqrt(D), False,
                           dtype),
            "wo": _dense(next(ks), (L, H * v, D), out_std, False, dtype)}


def _mlp(k, shape, n_layers, dtype) -> Dict[str, Any]:
    """A SwiGLU MLP; ``shape`` (..., D, F)."""
    *lead, D, F = shape
    lead = tuple(lead)
    ks = jax.random.split(k, 3)
    return {"w_up": _tn(ks[0], lead + (D, F), 1 / math.sqrt(D), dtype),
            "w_gate": _tn(ks[1], lead + (D, F), 1 / math.sqrt(D), dtype),
            "w_down": _tn(ks[2], lead + (F, D),
                          1 / math.sqrt(2 * n_layers * F), dtype)}


def _wrap(mlp):
    """Dense MLP leaves as the program keeps them: ``{"w": ...}``."""
    return {k: {"w": v} for k, v in mlp.items()}


def init_params(k: jax.Array, m: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded parameters described by the config's ``model`` dict (trace
    this under ``jax.jit``)."""
    if m.get("act") != "swiglu" or m.get("norm") != "rmsnorm":
        raise ValueError("this maker covers SwiGLU and RMSNorm")
    D, V, L = m["d_model"], m["vocab"], m["n_layers"]
    Ld = m["first_dense"]
    Lm = L - Ld
    E, held = m["n_experts"], m.get("experts_held") or m["n_experts"]
    ks = iter(jax.random.split(k, 16))
    dense = {"ln1": _norm(next(ks), (Ld, D), False, dtype),
             "ln2": _norm(next(ks), (Ld, D), False, dtype),
             "attn": _mla(next(ks), Ld, m, dtype),
             "mlp": _wrap(_mlp(next(ks), (Ld, D, m["d_ff"]), L, dtype))}
    moe = _mlp(next(ks), (Lm, held, D, m["moe_dff"]), L, dtype)
    moe["router"] = _tn(next(ks), (Lm, D, E), 1 / math.sqrt(D), dtype)
    moe["router_bias"] = BIAS_STD * jax.random.normal(next(ks), (Lm, E),
                                                      jnp.float32)
    experts = {"ln1": _norm(next(ks), (Lm, D), False, dtype),
               "ln2": _norm(next(ks), (Lm, D), False, dtype),
               "attn": _mla(next(ks), Lm, m, dtype),
               "moe": moe,
               "shared": _wrap(_mlp(next(ks), (Lm, D,
                                               m["n_shared"] * m["moe_dff"]),
                                    L, dtype))}
    params = {"embed": {"table": _tn(next(ks), (V, D), 1 / math.sqrt(D),
                                     dtype)},
              "blocks": [{"b0": dense}, {"b0": experts}],
              "final_ln": _norm(next(ks), (D,), False, dtype)}
    if m.get("tie_embeddings", False):
        raise ValueError("this maker covers an untied head")
    params["lm_head"] = {"w": _tn(next(ks), (D, V), 1 / math.sqrt(D), dtype)}
    return params


def make_params(seed: int, m: Dict[str, Any], dtype=jnp.bfloat16):
    """``init_params`` in one jitted call on the default device."""
    return jax.jit(lambda k: init_params(k, m, dtype))(key(seed))


def param_shapes(m: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(lambda k: init_params(k, m, dtype),
                          jax.random.PRNGKey(0))
