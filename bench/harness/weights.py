"""Weights and keys from ``--seed``, made on the device in one jitted call.

The tree has the layout the program's ``lm_init`` gives a dense decoder
(one scan group ``blocks[0]["b0"]`` whose leaves stack the layers), so the
program under test takes it as its parameters, and the float32 reference
(``harness/reference.py``) reads the same tree.  Norm scales and biases
are drawn near their usual values rather than left at 1 and 0, so that a
path that drops one shows in the comparison.  ``qkv_bias`` gives the
query, key and value projections a bias; ``out_bias`` (a key of the
benchmark's, not the program's config) gives one to the output and MLP
projections too, which the program adds wherever a ``b`` leaf is.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0xFFFFFFFF)


def _tn(k, shape, std, dtype):
    return (std * jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                              jnp.float32)).astype(dtype)


def _norm(k, shape, layernorm: bool, dtype) -> Dict[str, jax.Array]:
    ks, kb = jax.random.split(k)
    p = {"scale": (1.0 + 0.1 * jax.random.normal(ks, shape, jnp.float32)
                   ).astype(dtype)}
    if layernorm:
        p["bias"] = (0.02 * jax.random.normal(kb, shape, jnp.float32)
                     ).astype(dtype)
    return p


def _dense(k, shape, std, bias: bool, dtype) -> Dict[str, jax.Array]:
    kw, kb = jax.random.split(k)
    p = {"w": _tn(kw, shape, std, dtype)}
    if bias:
        p["b"] = (0.02 * jax.random.normal(kb, shape[:1] + shape[2:],
                                           jnp.float32)).astype(dtype)
    return p


def init_params(k: jax.Array, m: Dict[str, Any], dtype=jnp.float32):
    """Seeded parameters of a dense decoder described by the config's
    ``model`` dict (trace this under ``jax.jit``)."""
    L, D, H, Hkv, dh, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                              m["n_kv_heads"], m["d_head"], m["d_ff"],
                              m["vocab"])
    ln = m["norm"] == "layernorm"
    bias = bool(m.get("qkv_bias", False))
    out = bool(m.get("out_bias", False))
    ks = iter(jax.random.split(k, 16))
    attn = {"wq": _dense(next(ks), (L, D, H * dh), 1 / math.sqrt(D), bias,
                         dtype),
            "wk": _dense(next(ks), (L, D, Hkv * dh), 1 / math.sqrt(D), bias,
                         dtype),
            "wv": _dense(next(ks), (L, D, Hkv * dh), 1 / math.sqrt(D), bias,
                         dtype),
            "wo": _dense(next(ks), (L, H * dh, D),
                         1 / math.sqrt(2 * L * H * dh), out, dtype)}
    if m.get("qk_norm", False):
        attn["q_norm"] = _norm(next(ks), (L, dh), False, dtype)
        attn["k_norm"] = _norm(next(ks), (L, dh), False, dtype)
    mlp = {"w_up": _dense(next(ks), (L, D, F), 1 / math.sqrt(D), out,
                          dtype),
           "w_down": _dense(next(ks), (L, F, D), 1 / math.sqrt(2 * L * F),
                            out, dtype)}
    if m.get("act") in ("swiglu", "geglu"):
        mlp["w_gate"] = _dense(next(ks), (L, D, F), 1 / math.sqrt(D), out,
                               dtype)
    block = {"ln1": _norm(next(ks), (L, D), ln, dtype),
             "ln2": _norm(next(ks), (L, D), ln, dtype),
             "attn": attn, "mlp": mlp}
    params = {"embed": {"table": _tn(next(ks), (V, D), 1 / math.sqrt(D),
                                     dtype)},
              "blocks": [{"b0": block}],
              "final_ln": _norm(next(ks), (D,), ln, dtype)}
    if not m.get("tie_embeddings", False):
        params["lm_head"] = {"w": _tn(next(ks), (D, V), 1 / math.sqrt(D),
                                      dtype)}
    return params


def make_params(seed: int, m: Dict[str, Any], dtype=jnp.float32):
    """``init_params`` in one jitted call on the default device."""
    return jax.jit(lambda k: init_params(k, m, dtype))(key(seed))


def param_shapes(m: Dict[str, Any], dtype=jnp.float32):
    return jax.eval_shape(lambda k: init_params(k, m, dtype),
                          jax.random.PRNGKey(0))
