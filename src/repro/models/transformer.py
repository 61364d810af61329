"""Model assembly: decoder LMs, hybrid/SSM stacks, and encoder-decoders.

One `LMConfig` covers all 10 assigned architectures via a cyclic
``block_pattern`` (("attn",) for dense; ("rec","rec","attn") for
RecurrentGemma; 7×("mlstm",)+("slstm",) for xLSTM; MoE/MLA switches for the
DeepSeek family) plus an optional encoder stack for seamless-m4t.

Layers are stacked and iterated with jax.lax.scan (homogeneous "super
blocks" = one full pattern repetition), with per-superblock activation
rematerialization — this keeps HLO size and compile time independent of
depth and bounds activation memory for the 16 GB/chip budget.  Cross-
entropy streams over token chunks with the LM-head GEMM *inside* the chunk
loop so full fp32 logits (up to vocab 256k) are never materialized.

Every projection in the stack (attention q/k/v/o, MLP up/gate/down, MoE
experts, LM head) is an `mx_contract` custom VJP, so a training step's
GEMMs — forward, dgrad, and wgrad alike — dispatch to the fused MX Pallas
kernels in the per-pass formats carried by the (static) QuantConfig; remat
replays the quantized forward kernels during the backward pass, keeping
the recomputation on the same fused path.  Attention mixing is described
per layer by an `AttnSpec` built from the config (`attn_spec` /
`decode_spec`) and routed through ``mx_contract(kind="flash_attn" |
"attn_decode")`` — the flash Pallas kernels when fused, the bit-identical
tile-skipping oracle otherwise.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import AttnSpec, QuantConfig, mx_contract
from repro.core.mx import MXWeight, pack_mx
from repro.parallel.sharding import shard_act
from .layers import (COMPUTE_DTYPE, apply_norm, dense_init, embed_init,
                     embed_lookup, norm_init, qdense)
from .attention import (attention, attention_decode, attention_decode_paged,
                        attention_prefill, attention_prefill_chunk, attn_init)
from .mla import (mla_apply, mla_decode, mla_decode_paged, mla_init,
                  mla_prefill, mla_prefill_chunk)
from .mlp import mlp_apply, mlp_init
from .moe import moe_apply, moe_init
from .rglru import (rec_block_apply, rec_block_decode, rec_block_init,
                    rec_block_prefill)
from .xlstm import (mlstm_apply, mlstm_decode, mlstm_init, mlstm_prefill,
                    slstm_apply, slstm_decode, slstm_init, slstm_prefill)

__all__ = ["LMConfig", "lm_init", "lm_apply", "lm_loss", "init_cache",
           "init_cache_paged", "paged_leaf_mask", "kind_paged",
           "lm_decode_step", "lm_decode_step_routed", "lm_prefill",
           "lm_prefill_chunk",
           "prefill_supported", "chunk_supported", "block_plan",
           "pack_serving_weights"]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 64
    d_ff: int = 1024
    vocab: int = 512
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    act: str = "gelu"                # "gelu" | "relu" | "swiglu" | "geglu"
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e4
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0                # shared experts (DeepSeek/Moonlight)
    moe_dff: int = 0                 # per-expert hidden dim
    capacity_factor: float = 1.25    # training; serving routes dropless
    first_dense: int = 0             # leading dense layers before MoE ones
    router: str = "softmax"          # "softmax" | "sigmoid" (DeepSeek-V3)
    routed_scale: float = 1.0        # routed_scaling_factor on the gates
    # expert parallelism: this layer holds experts [first_expert,
    # first_expert + experts_held) of the router's n_experts (0 = all)
    experts_held: int = 0
    first_expert: int = 0
    # --- MLA (DeepSeek-V2/V3) ---
    mla: bool = False
    q_lora: int = 1536               # 0: queries by one w_q projection
    kv_lora: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_head: int = 128
    # --- hybrid / SSM ---
    block_pattern: Tuple[str, ...] = ("attn",)
    window: int = 0                  # local-attention window (0 = global)
    d_rnn: int = 0
    # --- encoder-decoder (seamless) ---
    enc_layers: int = 0
    # --- stub modality frontend: "none" | "patch" | "frames" ---
    frontend: str = "none"
    n_frontend_tokens: int = 0
    # --- execution ---
    scan_layers: bool = True
    remat: str = "full"              # "none" | "full" | "dots"
    q_chunk: int = 512
    kv_chunk: int = 1024
    loss_chunk: int = 2048

    @property
    def qk_dim(self) -> int:
        return (self.nope_dim + self.rope_dim) if self.mla else self.d_head

    def attn_spec(self, kind: str = "attn", *, causal: bool = True,
                  cache_len: int = 0) -> AttnSpec:
        """Training/prefill AttnSpec for a block kind.  Only "attn" blocks
        honor the local window ("dense_attn" lead layers and MLA attend
        globally); ``cache_len`` is set for prefill specs."""
        window = self.window if (kind == "attn" and not self.mla) else 0
        spec = AttnSpec.training(causal=causal, window=window,
                                 q_chunk=self.q_chunk,
                                 kv_chunk=self.kv_chunk)
        if cache_len:
            spec = dataclasses.replace(spec, cache_len=cache_len)
        return spec

    def decode_spec(self, kind: str = "attn", cache_len: int = 0,
                    page_size: int = 0) -> AttnSpec:
        """One-token decode AttnSpec (ring buffer for windowed layers;
        ``page_size > 0`` selects the paged-cache kind for eligible
        layers — windowed/ring layers keep their slab ring spec)."""
        window = self.window if (kind == "attn" and not self.mla) else 0
        if page_size > 0 and kind_paged(kind, self):
            return AttnSpec.decode(cache_len=cache_len, page_size=page_size)
        return AttnSpec.decode(window=window, cache_len=cache_len)

    def param_count(self, active_only: bool = False) -> int:
        """Analytic parameter count (total, or active-per-token for MoE)."""
        D, F = self.d_model, self.d_ff
        per_layer = {}
        if self.mla:
            q = (D * self.q_lora + self.q_lora * self.n_heads * self.qk_dim
                 if self.q_lora else D * self.n_heads * self.qk_dim)
            attn = (q + D * self.kv_lora + self.kv_lora * self.n_heads
                    * (self.nope_dim + self.v_head) + D * self.rope_dim
                    + self.n_heads * self.v_head * D)
        else:
            attn = D * self.n_heads * self.d_head \
                + 2 * D * self.n_kv_heads * self.d_head \
                + self.n_heads * self.d_head * D
        n_mats = 3 if self.act in ("swiglu", "geglu") else 2
        mlp = n_mats * D * F
        if self.n_experts:
            e = self.top_k if active_only else (self.experts_held
                                                or self.n_experts)
            moe = n_mats * D * self.moe_dff * (e + self.n_shared) \
                + D * self.n_experts
        else:
            moe = mlp
        per_layer["attn"] = attn + moe
        per_layer["rec"] = (3 * D * self.d_rnn + 2 * self.d_rnn ** 2
                            + self.d_rnn * D) + mlp
        d_in = 2 * D
        per_layer["mlstm"] = D * 2 * d_in + 3 * d_in * d_in + d_in * D
        per_layer["slstm"] = 4 * D * D + D * D + 3 * D * int(4 * D / 3)
        total = 0
        pat = self.block_pattern
        for i in range(self.n_layers):
            kind = pat[i % len(pat)]
            if self.n_experts and kind == "attn" and i < self.first_dense:
                total += attn + mlp
            else:
                total += per_layer[kind]
        total += self.enc_layers * (attn + mlp + (attn if False else 0))
        total += self.vocab * D * (1 if self.tie_embeddings else 2)
        return total


# --------------------------------------------------------------------------
# block plan: partition layers into scan groups of full pattern repetitions
# --------------------------------------------------------------------------
def block_plan(cfg: LMConfig) -> List[Tuple[Tuple[str, ...], int]]:
    pat = tuple(cfg.block_pattern)
    m = len(pat)
    n_layers = cfg.n_layers
    groups: List[Tuple[Tuple[str, ...], int]] = []
    # leading dense layers for MoE archs get their own group
    lead = cfg.first_dense if cfg.n_experts else 0
    if lead:
        groups.append((("dense_attn",) * 1, lead))
        n_layers -= lead
    n_rep, tail = divmod(n_layers, m)
    if n_rep:
        groups.append((pat, n_rep))
    if tail:
        groups.append((pat[:tail], 1))
    return groups


# --------------------------------------------------------------------------
# per-block init / apply / decode
# --------------------------------------------------------------------------
def _block_init(key, kind: str, cfg: LMConfig):
    ks = jax.random.split(key, 4)
    L = cfg.n_layers
    if kind in ("attn", "dense_attn", "enc_attn"):
        p = {"ln1": norm_init(cfg.d_model, cfg.norm),
             "ln2": norm_init(cfg.d_model, cfg.norm)}
        if cfg.mla and kind != "enc_attn":
            p["attn"] = mla_init(ks[0], cfg.d_model, cfg.n_heads, cfg.q_lora,
                                 cfg.kv_lora, cfg.nope_dim, cfg.rope_dim,
                                 cfg.v_head, L)
        else:
            p["attn"] = attn_init(ks[0], cfg.d_model, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.d_head, cfg.qk_norm,
                                  cfg.qkv_bias, L)
        if cfg.n_experts and kind == "attn":
            p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe_dff,
                                cfg.n_experts, cfg.act, L,
                                held=cfg.experts_held, router=cfg.router)
            if cfg.n_shared:
                p["shared"] = mlp_init(ks[2], cfg.d_model,
                                       cfg.n_shared * cfg.moe_dff, cfg.act, L)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, L)
        return p
    if kind == "dec_attn":
        return {"ln1": norm_init(cfg.d_model, cfg.norm),
                "attn": attn_init(ks[0], cfg.d_model, cfg.n_heads,
                                  cfg.n_kv_heads, cfg.d_head, cfg.qk_norm,
                                  cfg.qkv_bias, L),
                "ln_x": norm_init(cfg.d_model, cfg.norm),
                "xattn": attn_init(ks[1], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.d_head, cfg.qk_norm,
                                   cfg.qkv_bias, L),
                "ln2": norm_init(cfg.d_model, cfg.norm),
                "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.act, L)}
    if kind == "rec":
        return {"ln1": norm_init(cfg.d_model, cfg.norm),
                "rec": rec_block_init(ks[0], cfg.d_model, cfg.d_rnn, L),
                "ln2": norm_init(cfg.d_model, cfg.norm),
                "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, L)}
    if kind == "mlstm":
        return {"ln": norm_init(cfg.d_model, cfg.norm),
                "cell": mlstm_init(ks[0], cfg.d_model, cfg.n_heads, L)}
    if kind == "slstm":
        return {"ln": norm_init(cfg.d_model, cfg.norm),
                "cell": slstm_init(ks[0], cfg.d_model, cfg.n_heads, L)}
    raise ValueError(f"unknown block kind {kind!r}")


def _ffn(p, hn, cfg: LMConfig, qcfg: QuantConfig, *, serving: bool,
         token_mask=None):
    """The block's feed-forward on normed ``hn`` (B, T, D): the MLP, or the
    held experts' part of the routed sum plus the shared experts.  Returns
    (y, moe metrics or None).  Serving routes dropless; ``token_mask``
    ((B, T) bool) keeps padding and dead rows out of the routing."""
    if "moe" not in p:
        return mlp_apply(p["mlp"], hn, qcfg, cfg.act), None
    B, T, D = hn.shape
    y, metrics = moe_apply(
        p["moe"], hn.reshape(B * T, D), qcfg, top_k=cfg.top_k, act=cfg.act,
        capacity_factor=cfg.capacity_factor, router=cfg.router,
        routed_scale=cfg.routed_scale, first_expert=cfg.first_expert,
        dropless=serving,
        token_mask=None if token_mask is None else token_mask.reshape(-1))
    y = y.reshape(B, T, D)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], hn, qcfg, cfg.act)
    return y, metrics


def _routing(metrics):
    """A block's routing counters, or None for a block without experts."""
    if metrics is None:
        return None
    return {"held_tokens": metrics["held_tokens"],
            "dropped": metrics["dropped"]}


def _stack_routing(per_block, stacked: bool):
    """Routing counters of one scan group, ``per_block`` the pattern's
    blocks' counters (each None or stacked over the group's repetitions
    when ``stacked``), in layer order: {"held_tokens": (moe_layers,
    held), "dropped": ()} or None."""
    rs = [r for r in per_block if r is not None]
    if not rs:
        return None
    held = jnp.stack([r["held_tokens"] for r in rs],
                     axis=1 if stacked else 0)
    return {"held_tokens": held.reshape(-1, held.shape[-1]),
            "dropped": sum(jnp.sum(r["dropped"]) for r in rs)}


def _join_routing(groups):
    """Concatenate the groups' routing counters (None where none has
    experts)."""
    rs = [r for r in groups if r is not None]
    if not rs:
        return None
    return {"held_tokens": jnp.concatenate([r["held_tokens"] for r in rs]),
            "dropped": sum(r["dropped"] for r in rs).astype(jnp.int32)}


def _block_apply(h, p, kind: str, cfg: LMConfig, qcfg: QuantConfig,
                 positions, enc_out=None):
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "dense_attn", "enc_attn", "dec_attn"):
        hn = apply_norm(p["ln1"], h, qcfg, cfg.norm)
        if cfg.mla and kind not in ("enc_attn",):
            a = mla_apply(p["attn"], hn, qcfg=qcfg, n_heads=cfg.n_heads,
                          nope=cfg.nope_dim, rope_dim=cfg.rope_dim,
                          v_head=cfg.v_head, positions=positions,
                          spec=cfg.attn_spec(kind),
                          rope_theta=cfg.rope_theta)
        else:
            a = attention(p["attn"], hn, qcfg=qcfg, n_heads=cfg.n_heads,
                          n_kv=cfg.n_kv_heads, d_head=cfg.d_head,
                          positions=positions,
                          spec=cfg.attn_spec(
                              kind, causal=(kind != "enc_attn")),
                          rope_theta=cfg.rope_theta)
        h = h + a
        if kind == "dec_attn":
            hx = apply_norm(p["ln_x"], h, qcfg, cfg.norm)
            h = h + attention(p["xattn"], hx, qcfg=qcfg, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads, d_head=cfg.d_head,
                              positions=positions, xkv=enc_out,
                              spec=AttnSpec.training(
                                  causal=False, q_chunk=cfg.q_chunk,
                                  kv_chunk=cfg.kv_chunk))
        hn2 = apply_norm(p["ln2"], h, qcfg, cfg.norm)
        y, metrics = _ffn(p, hn2, cfg, qcfg, serving=False)
        if metrics is not None:
            aux = aux + metrics["aux_loss"]
        return h + y, aux
    if kind == "rec":
        h = h + rec_block_apply(p["rec"], apply_norm(p["ln1"], h, qcfg,
                                                     cfg.norm), qcfg)
        h = h + mlp_apply(p["mlp"], apply_norm(p["ln2"], h, qcfg, cfg.norm),
                          qcfg, cfg.act)
        return h, aux
    if kind == "mlstm":
        return h + mlstm_apply(p["cell"], apply_norm(p["ln"], h, qcfg,
                                                     cfg.norm),
                               qcfg, cfg.n_heads), aux
    if kind == "slstm":
        return h + slstm_apply(p["cell"], apply_norm(p["ln"], h, qcfg,
                                                     cfg.norm),
                               qcfg, cfg.n_heads), aux
    raise ValueError(kind)


# --------------------------------------------------------------------------
# stacks
# --------------------------------------------------------------------------
def _stack_init(key, cfg: LMConfig, plan, kind_override=None):
    groups = []
    for gi, (pattern, n_rep) in enumerate(plan):
        pat = [kind_override or k for k in pattern]
        gkey = jax.random.fold_in(key, gi)
        keys = jax.random.split(gkey, n_rep * len(pat)).reshape(
            n_rep, len(pat), 2)
        group = {}
        for j, kind in enumerate(pat):
            group[f"b{j}"] = jax.vmap(
                lambda k, kind=kind: _block_init(k, kind, cfg))(keys[:, j])
        groups.append(group)
    return groups


def _remat(fn, cfg: LMConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _stack_apply(x, groups, plan, cfg: LMConfig, qcfg: QuantConfig,
                 positions, enc_out=None, kind_override=None):
    aux_total = jnp.zeros((), jnp.float32)
    for (pattern, n_rep), gp in zip(plan, groups):
        pat = [kind_override or k for k in pattern]

        def body(h, layer_params, pat=pat):
            aux = jnp.zeros((), jnp.float32)
            h = shard_act(h)
            for j, kind in enumerate(pat):
                h, a = _block_apply(h, layer_params[f"b{j}"], kind, cfg,
                                    qcfg, positions, enc_out)
                aux = aux + a
            return shard_act(h), aux

        if cfg.scan_layers and n_rep > 1:
            body_fn = _remat(body, cfg)
            x, auxs = jax.lax.scan(body_fn, x, gp)
            aux_total = aux_total + jnp.sum(auxs)
        else:
            for r in range(n_rep):
                lp = jax.tree.map(lambda a, r=r: a[r], gp)
                x, a = _remat(body, cfg)(x, lp)
                aux_total = aux_total + a
    return x, aux_total


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
def _decoder_plan(cfg: LMConfig):
    plan = block_plan(cfg)
    if cfg.enc_layers:
        plan = [(("dec_attn",) * len(p), n) for p, n in plan]
    return plan


def lm_init(key, cfg: LMConfig):
    ks = jax.random.split(key, 5)
    params: Dict[str, Any] = {"embed": embed_init(ks[0], cfg.vocab,
                                                  cfg.d_model)}
    params["blocks"] = _stack_init(ks[1], cfg, _decoder_plan(cfg))
    params["final_ln"] = norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab,
                                       std=1.0 / math.sqrt(cfg.d_model))
    if cfg.enc_layers:
        enc_plan = [(("enc_attn",), cfg.enc_layers)]
        params["encoder"] = _stack_init(ks[3], cfg, enc_plan)
        params["enc_ln"] = norm_init(cfg.d_model, cfg.norm)
    if cfg.frontend != "none":
        params["frontend_proj"] = dense_init(ks[4], cfg.d_model, cfg.d_model)
    return params


def _encode(params, batch, cfg, qcfg):
    """Run the encoder stack over stub frame embeddings (audio frontend)."""
    frames = shard_act(batch["frames"].astype(COMPUTE_DTYPE))  # (B, Te, D)
    frames = qdense(params["frontend_proj"], frames, qcfg)
    B, Te, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(Te)[None], (B, Te))
    enc_plan = [(("enc_attn",), cfg.enc_layers)]
    h, _ = _stack_apply(frames, params["encoder"], enc_plan, cfg, qcfg, pos)
    return apply_norm(params["enc_ln"], h, qcfg, cfg.norm)


def _embed_inputs(params, batch, cfg, qcfg):
    """Token (+ optional patch-stub) embedding. Returns (h, positions)."""
    tok = batch["tokens"]
    h = embed_lookup(params["embed"], tok)
    if cfg.frontend == "patch":
        patches = batch["patch_embeds"].astype(COMPUTE_DTYPE)  # (B, Np, D)
        patches = qdense(params["frontend_proj"], patches, qcfg)
        h = jnp.concatenate([patches, h], axis=1)
    h = shard_act(h)
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return h, positions


def lm_apply(params, batch, cfg: LMConfig, qcfg: QuantConfig):
    """Forward to final hidden states. Returns (hidden, aux_loss)."""
    h, positions = _embed_inputs(params, batch, cfg, qcfg)
    enc_out = _encode(params, batch, cfg, qcfg) if cfg.enc_layers else None
    h, aux = _stack_apply(h, params["blocks"], _decoder_plan(cfg), cfg, qcfg,
                          positions, enc_out)
    h = apply_norm(params["final_ln"], h, qcfg, cfg.norm)
    return h, aux


def _head_matmul(params, h, cfg, qcfg):
    # A tied head packed for serving sits under "lm_head" beside the table.
    if "lm_head" in params:
        return qdense(params["lm_head"], h, qcfg)
    w = params["embed"]["table"].astype(h.dtype).T
    return mx_contract(h, w, qcfg, kind="dense")


def pack_serving_weights(params, cfg: LMConfig, qcfg: QuantConfig):
    """The weights a server holds: every dense projection that ``qdense``
    or the tied head consumes, MX-packed at rest (:class:`MXWeight`), so
    the forward GEMMs dequantize codes on load instead of re-quantizing
    bf16 weights on every call.

    Packs only where ``qcfg`` quantizes weights (``w_fwd``, one byte an
    element) and K is a multiple of ``qcfg.block``, from the weight in the
    compute dtype, as ``qdense`` casts it before quantizing.  Every other
    leaf is the caller's own array, untouched: embeddings (lookup), norms,
    biases, MoE experts (the "bmm" kind) and MLA attention, whose absorbed
    decode reads ``w_uk``/``w_uv`` raw.  A tied head is packed from
    ``table.T`` into ``lm_head``; the table stays for the lookup.  Returns
    ``(params, stats)``: ``packed_weight_share`` is the share of dense
    projection parameters served packed, ``packed_weight_bytes`` their
    bytes at rest.  The caller's ``params`` are neither mutated nor
    donated; the result holds no bf16 copy of a packed leaf."""
    fmt = qcfg.w_fwd
    if fmt is None or fmt.bits > 8:
        return params, {"packed_weight_share": 0.0,
                        "packed_weight_bytes": 0.0}
    seen = {"total": 0, "packed": 0, "bytes": 0}

    def pack(w, raw=False):
        seen["total"] += w.size
        if raw or w.shape[-2] % qcfg.block:
            return w
        mw = pack_mx(w, fmt, qcfg.block, qcfg.scale_mode, COMPUTE_DTYPE)
        seen["packed"] += w.size
        seen["bytes"] += mw.nbytes
        return mw

    def walk(node, mla=False):
        if isinstance(node, (list, tuple)):
            return type(node)(walk(x, mla) for x in node)
        if not isinstance(node, dict):
            return node
        mla = mla or "w_uk" in node
        return {k: pack(v, mla) if k == "w" and hasattr(v, "ndim")
                and not isinstance(v, MXWeight) else walk(v, mla)
                for k, v in node.items()}

    packed = walk(params)
    if cfg.tie_embeddings and "lm_head" not in params:
        head = pack(params["embed"]["table"].T)
        if isinstance(head, MXWeight):
            packed["lm_head"] = {"w": head}
    stats = {"packed_weight_share": seen["packed"] / max(seen["total"], 1),
             "packed_weight_bytes": float(seen["bytes"])}
    return packed, stats


def lm_loss(params, batch, cfg: LMConfig, qcfg: QuantConfig):
    """Mean next-token cross-entropy; logits streamed over sequence chunks.

    Chunking runs along T (batch stays sharded on the data axis every
    step); the LM-head GEMM sits inside the chunk loop so fp32 logits peak
    at (B_local, loss_chunk, vocab_local)."""
    h, aux = lm_apply(params, batch, cfg, qcfg)
    labels = batch["labels"]
    if cfg.frontend == "patch":                # loss only on the text tail
        h = h[:, -labels.shape[1]:]
    B, T, D = h.shape
    mask = (labels >= 0).astype(jnp.float32)
    lc = min(cfg.loss_chunk, T)
    n_chunks = (T + lc - 1) // lc
    pad = n_chunks * lc - T
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n_chunks, lc, D).transpose(1, 0, 2, 3)
    lcs = labels.reshape(B, n_chunks, lc).transpose(1, 0, 2)
    ms = mask.reshape(B, n_chunks, lc).transpose(1, 0, 2)

    def chunk(carry, xs):
        hcx, lx, mx = xs                       # (B, lc, D), (B, lc)
        logits = _head_matmul(params, hcx, cfg, qcfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lx, 0)[..., None],
                                 axis=-1)[..., 0]
        return carry + jnp.sum((lse - ll) * mx), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                            (hc, lcs, ms))
    loss = total / jnp.maximum(jnp.sum(mask), 1.0)
    metrics = {"loss": loss, "aux_loss": aux}
    return loss + 0.01 * aux, metrics


# --------------------------------------------------------------------------
# decode (serving)
# --------------------------------------------------------------------------
def _cache_init(kind: str, cfg: LMConfig, B: int, S: int):
    dt = COMPUTE_DTYPE
    if kind in ("attn", "dense_attn"):
        # Only "attn" blocks honor the local window (ring buffer);
        # "dense_attn" lead layers attend globally in decode/prefill.
        s = min(S, cfg.window) if (cfg.window and kind == "attn") else S
        shp = (B, s, cfg.n_kv_heads, cfg.d_head)
        if cfg.mla:
            return {"ckv": jnp.zeros((B, S, cfg.kv_lora), dt),
                    "kr": jnp.zeros((B, S, cfg.rope_dim), dt)}
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
    if kind == "dec_attn":
        shp = (B, S, cfg.n_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
    if kind == "rec":
        return {"conv": jnp.zeros((B, 3, cfg.d_rnn), dt),
                "h": jnp.zeros((B, cfg.d_rnn), jnp.float32)}
    if kind == "mlstm":
        d_in = 2 * cfg.d_model
        dh = d_in // cfg.n_heads
        return {"conv": jnp.zeros((B, 3, d_in), dt),
                "C": jnp.zeros((B, cfg.n_heads, dh, dh), jnp.float32),
                "n": jnp.zeros((B, cfg.n_heads, dh), jnp.float32),
                "m": jnp.full((B, cfg.n_heads), -1e30, jnp.float32)}
    if kind == "slstm":
        dh = cfg.d_model // cfg.n_heads
        z = lambda: jnp.zeros((B, cfg.n_heads, dh), jnp.float32)
        return {"c": z(), "n": z(), "m": jnp.full((B, cfg.n_heads, dh),
                                                  -1e30, jnp.float32),
                "h": z()}
    raise ValueError(kind)


def init_cache(cfg: LMConfig, B: int, S: int):
    plan = _decoder_plan(cfg)
    caches = []
    for pattern, n_rep in plan:
        g = {}
        for j, kind in enumerate(pattern):
            one = _cache_init(kind, cfg, B, S)
            g[f"b{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), one)
        caches.append(g)
    return caches


# --------------------------------------------------------------------------
# paged cache (serving)
# --------------------------------------------------------------------------
def kind_paged(kind: str, cfg: LMConfig) -> bool:
    """Whether a block kind's decode state lives in page pools.  Global
    attention (and MLA latents) page; ring-buffer windowed layers and
    recurrent/xLSTM state keep the slab layout (their state is O(window) /
    O(1) per row — nothing to page)."""
    if kind not in ("attn", "dense_attn"):
        return False
    if cfg.mla:
        return True
    return not (cfg.window and kind == "attn")


def _paged_cache_init(kind: str, cfg: LMConfig, n_pages: int,
                      page_size: int):
    """Page-pool leaves for one paged block: global pools shared across
    batch rows through the engine's page table — head-major (N, Hkv, ps, d)
    K/V pools (one (ps, d) tile per head and page, what the paged decode
    kernel DMAs), (N, ps, ·) MLA latent pools."""
    dt = COMPUTE_DTYPE
    if cfg.mla:
        return {"ckv": jnp.zeros((n_pages, page_size, cfg.kv_lora), dt),
                "kr": jnp.zeros((n_pages, page_size, cfg.rope_dim), dt)}
    shp = (n_pages, cfg.n_kv_heads, page_size, cfg.d_head)
    return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}


def init_cache_paged(cfg: LMConfig, B: int, S: int, n_pages: int,
                     page_size: int):
    """Paged decode cache: eligible attention layers get (N, ps, ·) page
    pools (one pool per layer, one shared page table); every other kind
    keeps its slab entry from ``_cache_init`` (the slab fallback).  ``S``
    sizes the slab leaves (= the per-row logical capacity P*ps)."""
    plan = _decoder_plan(cfg)
    caches = []
    for pattern, n_rep in plan:
        g = {}
        for j, kind in enumerate(pattern):
            if kind_paged(kind, cfg):
                one = _paged_cache_init(kind, cfg, n_pages, page_size)
            else:
                one = _cache_init(kind, cfg, B, S)
            g[f"b{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), one)
        caches.append(g)
    return caches


def paged_leaf_mask(cfg: LMConfig):
    """Pytree (same structure as ``init_cache_paged``'s result) of bools:
    True for page-pool leaves, False for slab leaves — what the engine's
    page-zeroing / gather / scatter helpers map over."""
    plan = _decoder_plan(cfg)
    out = []
    for pattern, n_rep in plan:
        g = {}
        for j, kind in enumerate(pattern):
            paged = kind_paged(kind, cfg)
            proto = (_paged_cache_init(kind, cfg, 1, 1) if paged
                     else _cache_init(kind, cfg, 1, 1))
            g[f"b{j}"] = jax.tree.map(lambda a, p=paged: p, proto)
        out.append(g)
    return out


def _block_decode(h, p, cache, kind, cfg, qcfg, pos, enc_out=None,
                  page_table=None, page_size: int = 0, token_mask=None):
    """One block of a decode step: (h, new cache entry, routing counters
    or None)."""
    if kind in ("attn", "dense_attn", "dec_attn"):
        hn = apply_norm(p["ln1"], h, qcfg, cfg.norm)
        paged = (page_table is not None and page_size > 0
                 and kind_paged(kind, cfg))
        if cfg.mla and paged:
            a, new_cache = mla_decode_paged(
                p["attn"], hn, cache, qcfg=qcfg, n_heads=cfg.n_heads,
                nope=cfg.nope_dim, rope_dim=cfg.rope_dim, v_head=cfg.v_head,
                pos=pos, page_table=page_table, page_size=page_size,
                rope_theta=cfg.rope_theta)
        elif cfg.mla:
            a, new_cache = mla_decode(p["attn"], hn, cache, qcfg=qcfg,
                                      n_heads=cfg.n_heads, nope=cfg.nope_dim,
                                      rope_dim=cfg.rope_dim, v_head=cfg.v_head,
                                      pos=pos, rope_theta=cfg.rope_theta)
        elif paged:
            S_view = page_table.shape[1] * page_size
            a, new_cache = attention_decode_paged(
                p["attn"], hn, cache, qcfg=qcfg, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, d_head=cfg.d_head, pos=pos,
                page_table=page_table,
                spec=cfg.decode_spec(kind, cache_len=S_view,
                                     page_size=page_size),
                rope_theta=cfg.rope_theta)
        else:
            a, new_cache = attention_decode(
                p["attn"], hn, cache, qcfg=qcfg, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, d_head=cfg.d_head, pos=pos,
                spec=cfg.decode_spec(kind), rope_theta=cfg.rope_theta)
        h = h + a
        if kind == "dec_attn" and enc_out is not None:
            hx = apply_norm(p["ln_x"], h, qcfg, cfg.norm)
            B = h.shape[0]
            positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                         (B,))[:, None]
            h = h + attention(p["xattn"], hx, qcfg=qcfg, n_heads=cfg.n_heads,
                              n_kv=cfg.n_kv_heads, d_head=cfg.d_head,
                              positions=positions, xkv=enc_out,
                              spec=AttnSpec.training(
                                  causal=False, q_chunk=1,
                                  kv_chunk=cfg.kv_chunk))
        hn2 = apply_norm(p["ln2"], h, qcfg, cfg.norm)
        y, metrics = _ffn(p, hn2, cfg, qcfg, serving=True,
                          token_mask=None if token_mask is None
                          else token_mask[:, None])
        return h + y, new_cache, _routing(metrics)
    if kind == "rec":
        a, new_cache = rec_block_decode(
            p["rec"], apply_norm(p["ln1"], h, qcfg, cfg.norm), cache, qcfg)
        h = h + a
        h = h + mlp_apply(p["mlp"], apply_norm(p["ln2"], h, qcfg, cfg.norm),
                          qcfg, cfg.act)
        return h, new_cache, None
    if kind == "mlstm":
        a, new_cache = mlstm_decode(p["cell"],
                                    apply_norm(p["ln"], h, qcfg, cfg.norm),
                                    cache, qcfg, cfg.n_heads)
        return h + a, new_cache, None
    if kind == "slstm":
        a, new_cache = slstm_decode(p["cell"],
                                    apply_norm(p["ln"], h, qcfg, cfg.norm),
                                    cache, qcfg, cfg.n_heads)
        return h + a, new_cache, None
    raise ValueError(kind)


def lm_decode_step(params, cache, tok, pos, cfg: LMConfig,
                   qcfg: QuantConfig, enc_out=None, page_table=None,
                   page_size: int = 0):
    """One decode step.  tok: (B, 1) int32; pos: scalar int32 (whole batch
    at the same position) or (B,) int32 per-row positions — the latter is
    what the continuous-batching scheduler uses, where each slot sits at
    its own sequence length.

    With ``page_table`` ((B, P) int32) and ``page_size`` set, eligible
    attention layers read/write (N, ps, ·) page pools (``init_cache_paged``)
    instead of per-row slabs; slab-fallback leaves behave as before.

    Returns (logits (B, vocab), new_cache)."""
    logits, new_caches, _ = lm_decode_step_routed(
        params, cache, tok, pos, cfg, qcfg, enc_out, page_table, page_size)
    return logits, new_caches


def lm_decode_step_routed(params, cache, tok, pos, cfg: LMConfig,
                          qcfg: QuantConfig, enc_out=None, page_table=None,
                          page_size: int = 0, token_mask=None):
    """``lm_decode_step`` that also returns the step's routing counters:
    (logits, new_cache, routing), ``routing`` None for a model without
    experts, else {"held_tokens": (moe_layers, held) int32 tokens routed
    to each held expert, "dropped": int32}.  ``token_mask`` ((B,) bool)
    keeps rows out of the routing (a serving batch's dead rows)."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tok.shape[0],))
    h = shard_act(embed_lookup(params["embed"], tok))
    plan = _decoder_plan(cfg)
    new_caches, routing = [], []
    for (pattern, n_rep), gp, gc in zip(plan, params["blocks"], cache):
        def body(h, xs, pattern=pattern):
            lp, lc = xs
            new_lc, rs = {}, []
            for j, kind in enumerate(pattern):
                h, nc, r = _block_decode(h, lp[f"b{j}"], lc[f"b{j}"], kind,
                                         cfg, qcfg, pos, enc_out,
                                         page_table=page_table,
                                         page_size=page_size,
                                         token_mask=token_mask)
                new_lc[f"b{j}"] = nc
                rs.append(r)
            return h, new_lc, rs

        if cfg.scan_layers and n_rep > 1:
            # The stacked cache rides in the carry, each layer's entry
            # written back in place: as scan outputs the new entries
            # would fill a second cache-sized buffer before the (donated)
            # result, about 4 GB at starcoder2-3b's page pool.
            def layer(carry, lp, body=body):
                h, gc, r = carry
                lc = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, False),
                    gc)
                h, nc, rs = body(h, (lp, lc))
                gc = jax.tree.map(
                    lambda a, n: jax.lax.dynamic_update_index_in_dim(
                        a, n.astype(a.dtype), r, 0), gc, nc)
                return (h, gc, r + 1), rs

            (h, new_gc, _), rs = jax.lax.scan(layer, (h, gc, 0), gp)
            routing.append(_stack_routing(rs, stacked=True))
        else:
            new_gc_list = []
            for r in range(n_rep):
                lp = jax.tree.map(lambda a, r=r: a[r], gp)
                lc = jax.tree.map(lambda a, r=r: a[r], gc)
                h, nc, rs = body(h, (lp, lc))
                new_gc_list.append(nc)
                routing.append(_stack_routing(rs, stacked=False))
            new_gc = jax.tree.map(lambda *xs: jnp.stack(xs), *new_gc_list)
        new_caches.append(new_gc)
    h = apply_norm(params["final_ln"], h, qcfg, cfg.norm)
    logits = _head_matmul(params, h[:, 0], cfg, qcfg)
    return logits, new_caches, _join_routing(routing)


# --------------------------------------------------------------------------
# fused prefill (serving)
# --------------------------------------------------------------------------
def prefill_supported(cfg: LMConfig) -> bool:
    """Whether ``lm_prefill`` covers this config (any decoder-only stack);
    encoder-decoder and modality-frontend configs fall back to
    token-stepping in the serving engine."""
    return cfg.enc_layers == 0 and cfg.frontend == "none"


def _block_prefill(h, p, kind, cfg: LMConfig, qcfg: QuantConfig, positions,
                   cache_len: int):
    """Full-sequence block forward that also emits the decode-cache entry
    (the fused counterpart of ``_block_decode``)."""
    if kind in ("attn", "dense_attn"):
        hn = apply_norm(p["ln1"], h, qcfg, cfg.norm)
        if cfg.mla:
            a, nc = mla_prefill(p["attn"], hn, qcfg=qcfg, n_heads=cfg.n_heads,
                                nope=cfg.nope_dim, rope_dim=cfg.rope_dim,
                                v_head=cfg.v_head, positions=positions,
                                spec=cfg.attn_spec(kind, cache_len=cache_len),
                                rope_theta=cfg.rope_theta)
        else:
            a, nc = attention_prefill(
                p["attn"], hn, qcfg=qcfg, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, d_head=cfg.d_head, positions=positions,
                spec=cfg.attn_spec(kind, cache_len=cache_len),
                rope_theta=cfg.rope_theta)
        h = h + a
        hn2 = apply_norm(p["ln2"], h, qcfg, cfg.norm)
        y, _ = _ffn(p, hn2, cfg, qcfg, serving=True)
        return h + y, nc
    if kind == "rec":
        a, nc = rec_block_prefill(p["rec"],
                                  apply_norm(p["ln1"], h, qcfg, cfg.norm),
                                  qcfg)
        h = h + a
        h = h + mlp_apply(p["mlp"], apply_norm(p["ln2"], h, qcfg, cfg.norm),
                          qcfg, cfg.act)
        return h, nc
    if kind == "mlstm":
        a, nc = mlstm_prefill(p["cell"],
                              apply_norm(p["ln"], h, qcfg, cfg.norm),
                              qcfg, cfg.n_heads)
        return h + a, nc
    if kind == "slstm":
        a, nc = slstm_prefill(p["cell"],
                              apply_norm(p["ln"], h, qcfg, cfg.norm),
                              qcfg, cfg.n_heads)
        return h + a, nc
    raise ValueError(f"fused prefill does not support block kind {kind!r}")


def lm_prefill(params, tokens, cfg: LMConfig, qcfg: QuantConfig,
               max_len: int, logit_positions=None):
    """Fused single-pass prefill: one full forward builds the decode cache.

    The production replacement for feeding a prompt token-by-token through
    ``lm_decode_step`` (T jitted steps → 1 fused pass; GEMMs go through the
    same MX ``qcfg`` as training).  tok: (B, T) int32 with T <= max_len.

    ``logit_positions`` (optional (B,) int32, default T-1 everywhere)
    selects the position whose logits are returned per row — the serving
    engine right-pads prompts to shape buckets and asks for the logits at
    each true prompt end (later decode steps overwrite padded cache slots
    before they ever become attendable, so padding is causally inert for
    positional caches).

    Returns (logits (B, vocab), cache) with ``cache`` exactly matching the
    ``init_cache`` tree, ready for ``lm_decode_step`` at position T.

    MoE routing is dropless here as in decode, so a batched prompt routes
    each token as token-stepped warmup would (the engine still never pads
    MoE prompts, see ServeEngine.pad_safe: padded tokens would route).
    """
    if not prefill_supported(cfg):
        raise NotImplementedError(
            "fused prefill covers decoder-only stacks; encoder-decoder / "
            "frontend configs use token-stepped warmup")
    B, T = tokens.shape
    h = shard_act(embed_lookup(params["embed"], tokens))
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    plan = _decoder_plan(cfg)
    caches = []
    for (pattern, n_rep), gp in zip(plan, params["blocks"]):
        def body(h, lp, pattern=pattern):
            nc = {}
            for j, kind in enumerate(pattern):
                h, c = _block_prefill(h, lp[f"b{j}"], kind, cfg, qcfg,
                                      positions, max_len)
                nc[f"b{j}"] = c
            return h, nc

        if cfg.scan_layers and n_rep > 1:
            h, gc = jax.lax.scan(body, h, gp)
        else:
            gc_list = []
            for r in range(n_rep):
                lp = jax.tree.map(lambda a, r=r: a[r], gp)
                h, c = body(h, lp)
                gc_list.append(c)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *gc_list)
        caches.append(gc)
    h = apply_norm(params["final_ln"], h, qcfg, cfg.norm)
    if logit_positions is None:
        logit_positions = jnp.full((B,), T - 1, jnp.int32)
    h_last = h[jnp.arange(B), logit_positions]          # (B, D)
    logits = _head_matmul(params, h_last, cfg, qcfg)
    return logits, caches


# --------------------------------------------------------------------------
# chunked prefill (serving)
# --------------------------------------------------------------------------
def chunk_supported(cfg: LMConfig) -> bool:
    """Whether ``lm_prefill_chunk`` covers this config: a global-attention
    decoder stack, GQA or MLA, with MLPs or experts (and its leading dense
    layers).  Windowed/ring and recurrent configs prefill whole
    (``lm_prefill``) and are pagified afterwards — their prefix state is
    not an append-only sequence (ring slots, RNN state)."""
    return (prefill_supported(cfg) and cfg.window == 0 and cfg.d_rnn == 0
            and set(cfg.block_pattern) <= {"attn"})


def lm_prefill_chunk(params, tokens, prior, start: int, cfg: LMConfig,
                     qcfg: QuantConfig, logit_positions=None,
                     kv_mask=None):
    """One chunk of a continuous prefill: forward ``tokens`` (B, C) at
    absolute positions ``start .. start+C-1`` attending the already-written
    prefix through ``prior`` — a cache-shaped tree whose attention leaves
    hold the gathered (n_rep, B, start, Hkv, d) prefix K/V, or the
    (n_rep, B, start, ·) MLA latents (empty leading chunks pass start=0
    arrays).

    Returns (logits (B, vocab) at ``logit_positions`` (default C-1),
    chunk_kv, routing) where chunk_kv mirrors the cache structure with the
    chunk's (n_rep, B, C, Hkv, d) K/V (or (n_rep, B, C, ·) latents) for
    the caller to write into fresh pages, and ``routing`` is the chunk's
    routing counters as ``lm_decode_step_routed`` gives them (None
    without experts).  ``kv_mask`` ((B, C) bool) zeroes padded tail K/V
    and keeps those positions out of the routing, so a fixed chunk shape
    can carry a shorter final chunk."""
    if not chunk_supported(cfg):
        raise NotImplementedError(
            "chunked prefill covers global-attention decoder stacks; "
            "other configs prefill whole and pagify")
    B, C = tokens.shape
    h = shard_act(embed_lookup(params["embed"], tokens))
    positions = jnp.broadcast_to(jnp.arange(start, start + C)[None], (B, C))
    plan = _decoder_plan(cfg)
    chunk_caches, routing = [], []
    for (pattern, n_rep), gp, gc in zip(plan, params["blocks"], prior):
        def body(h, xs, pattern=pattern):
            lp, lc = xs
            nc, rs = {}, []
            for j, kind in enumerate(pattern):
                p, c = lp[f"b{j}"], lc[f"b{j}"]
                hn = apply_norm(p["ln1"], h, qcfg, cfg.norm)
                spec = cfg.attn_spec(kind).with_offset(start)
                if cfg.mla:
                    a, ckv, kr = mla_prefill_chunk(
                        p["attn"], hn, c["ckv"], c["kr"], qcfg=qcfg,
                        n_heads=cfg.n_heads, nope=cfg.nope_dim,
                        rope_dim=cfg.rope_dim, v_head=cfg.v_head,
                        positions=positions, spec=spec, kv_mask=kv_mask,
                        rope_theta=cfg.rope_theta)
                    nc[f"b{j}"] = {"ckv": ckv, "kr": kr}
                else:
                    a, ck, cv = attention_prefill_chunk(
                        p["attn"], hn, c["k"], c["v"], qcfg=qcfg,
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        d_head=cfg.d_head, positions=positions, spec=spec,
                        kv_mask=kv_mask, rope_theta=cfg.rope_theta)
                    nc[f"b{j}"] = {"k": ck, "v": cv}
                h = h + a
                hn2 = apply_norm(p["ln2"], h, qcfg, cfg.norm)
                y, metrics = _ffn(p, hn2, cfg, qcfg, serving=True,
                                  token_mask=kv_mask)
                h = h + y
                rs.append(_routing(metrics))
            return h, (nc, rs)

        if cfg.scan_layers and n_rep > 1:
            h, (cc, rs) = jax.lax.scan(body, h, (gp, gc))
            routing.append(_stack_routing(rs, stacked=True))
        else:
            cc_list = []
            for r in range(n_rep):
                lp = jax.tree.map(lambda a, r=r: a[r], gp)
                lc = jax.tree.map(lambda a, r=r: a[r], gc)
                h, (c, rs) = body(h, (lp, lc))
                cc_list.append(c)
                routing.append(_stack_routing(rs, stacked=False))
            cc = jax.tree.map(lambda *xs: jnp.stack(xs), *cc_list)
        chunk_caches.append(cc)
    h = apply_norm(params["final_ln"], h, qcfg, cfg.norm)
    if logit_positions is None:
        logit_positions = jnp.full((B,), C - 1, jnp.int32)
    h_last = h[jnp.arange(B), logit_positions]          # (B, D)
    logits = _head_matmul(params, h_last, cfg, qcfg)
    return logits, chunk_caches, _join_routing(routing)
