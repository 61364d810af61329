"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a *described* chip: what Mosaic refuses here (an in-kernel
reshape that splits the lane dim, a block that breaks the (8, 128) tiling
rule, a tile over the scoped VMEM limit) it would refuse on the chip.
Interpret-mode tests cannot see any of that, so these compiles guard the
kernels at olmo-paper widths (d_model 512, d_ff 2048, vocab 32000,
d_head 64, context 512), the packed serving GEMM at starcoder2-3b's, and
the held experts' GEMMs at Moonlight-16B-A3B's.
Nothing runs: a compile that passes is not a
chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.formats import E4M3
from repro.kernels.mx_attention import (mx_attn_bwd_pallas,
                                        mx_attn_decode_paged_pallas,
                                        mx_attn_decode_pallas,
                                        mx_attn_fwd_pallas)
from repro.kernels.mx_matmul import mx_matmul_pallas
from repro.kernels.mx_matmul_bwd import (mx_matmul_dgrad_pallas,
                                         mx_matmul_wgrad_pallas)
from repro.kernels.mx_quant import mx_quantize_pallas

TOKENS = 4096          # 8 sequences of 512
D, FF, VOCAB, DH, T = 512, 2048, 32000, 64, 512
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("m,k,n", [(TOKENS, D, FF), (TOKENS, FF, D),
                                   (TOKENS, D, VOCAB)], ids=str)
def test_mx_matmul_fwd_compiles(one_chip, m, k, n):
    _compile(one_chip, lambda a, b: mx_matmul_pallas(a, b, E4M3, E4M3),
             ((m, k), BF16), ((k, n), BF16))


@pytest.mark.parametrize("m", [64, 256])
@pytest.mark.parametrize("k,n", [(3072, 12288), (12288, 3072), (3072, 256)],
                         ids=str)
def test_mx_matmul_packed_compiles(one_chip, m, k, n):
    """The dequantize-on-load path at starcoder2-3b's serving shapes: a
    64-row decode step and a 256-row prefill chunk against w_up, w_down
    and wk/wv packed (E4M3 codes + E8M0 exponents, one byte each).  It
    keeps the kernel's label, which the benchmark's roofline reads."""
    from repro.core.mx import MXWeight
    text = _compile(
        one_chip,
        lambda a, c, e: mx_matmul_pallas(a, MXWeight(c, e, E4M3), None,
                                         None),
        ((m, k), BF16), ((k, n), jnp.uint8), ((k // 32, n), jnp.uint8))
    assert "%mx_matmul_pallas" in text


@pytest.mark.parametrize("m", [64, 256])
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)], ids=str)
def test_held_expert_bmm_compiles_under_its_own_label(one_chip, monkeypatch,
                                                      m, k, n):
    """Moonlight-16B-A3B's eight held experts (up/gate 2048 x 1408, down
    1408 x 2048) on a dropless 64-row decode step and a 256-row prefill
    chunk, bf16 weights quantized on load, as ``mx_contract(kind="bmm")``
    runs them: the kernel reads as ``mx_expert_matmul_pallas`` and not as
    the dense projections' ``mx_matmul_pallas``, which the benchmark's
    expert and dense roofline metrics tell apart."""
    import repro.kernels.ops as ops
    from repro.core import mx_contract, preset, use_fused_gemms
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    qcfg = preset("e4m3_bf16act")
    with use_fused_gemms(True):
        text = _compile(one_chip,
                        lambda x, w: mx_contract(x, w, qcfg, kind="bmm"),
                        ((8, m, k), BF16), ((8, k, n), BF16))
    assert "%mx_expert_matmul_pallas" in text
    assert "%mx_matmul_pallas" not in text


@pytest.mark.parametrize("m,k,n", [(TOKENS, D, FF), (TOKENS, D, VOCAB)],
                         ids=str)
def test_mx_matmul_dgrad_compiles(one_chip, m, k, n):
    _compile(one_chip,
             lambda dy, w: mx_matmul_dgrad_pallas(dy, w, E4M3, E4M3),
             ((m, n), BF16), ((k, n), BF16))


def test_mx_matmul_wgrad_compiles(one_chip):
    _compile(one_chip,
             lambda x, dy: mx_matmul_wgrad_pallas(x, dy, E4M3, E4M3),
             ((TOKENS, D), BF16), ((TOKENS, FF), BF16))


@pytest.mark.parametrize("k", [D, FF, VOCAB])
def test_mx_quantize_compiles(one_chip, k):
    _compile(one_chip, lambda x: mx_quantize_pallas(x, E4M3),
             ((TOKENS, k), BF16))


def _spec():
    return get_config("olmo-paper", "full").attn_spec()


def test_mx_flash_fwd_compiles(one_chip):
    spec = _spec()
    _compile(one_chip, lambda q, k, v: mx_attn_fwd_pallas(q, k, v, E4M3,
                                                          spec),
             ((16, 1, T, DH), BF16), ((16, T, DH), BF16),
             ((16, T, DH), BF16))


def test_mx_flash_bwd_compiles(one_chip):
    spec = _spec()
    _compile(one_chip,
             lambda q, k, v, do, o, lse: mx_attn_bwd_pallas(
                 q, k, v, do, o, lse, E4M3, spec),
             ((16, 1, T, DH), BF16), ((16, T, DH), BF16),
             ((16, T, DH), BF16), ((16, 1, T, DH), BF16),
             ((16, 1, T, DH), BF16), ((16, 1, T), jnp.float32))


def test_mx_decode_compiles(one_chip):
    _compile(one_chip,
             lambda q, k, v, ok: mx_attn_decode_pallas(q, k, v, ok, E4M3),
             ((64, 1, DH), BF16), ((64, T, DH), BF16), ((64, T, DH), BF16),
             ((64, T), jnp.bool_))


@pytest.mark.parametrize("view,dh", [(T, DH), (4096, 128)], ids=str)
@pytest.mark.parametrize("fmt", [None, E4M3], ids=["bf16", "e4m3"])
def test_paged_decode_compiles(one_chip, fmt, view, dh):
    """At olmo-paper's context, and at a 4096-position view of 128-wide
    heads: the kernel keeps one head's whole view in VMEM scratch, and
    4096 positions fit the default scoped limit (under E4M3, 8192 do
    not)."""
    B, H, ps, N = 8, 8, 32, 64
    P = view // ps
    _compile(one_chip,
             lambda q, kp, vp, pt, ok: mx_attn_decode_paged_pallas(
                 q, kp, vp, pt, ok, fmt),
             ((B * H, 1, dh), BF16), ((N, H, ps, dh), BF16),
             ((N, H, ps, dh), BF16), ((B, P), jnp.int32),
             ((B, P * ps), jnp.bool_))


def test_paged_serve_step_packed_updates_pool_in_place(one_chip,
                                                       monkeypatch):
    """The paged decode step at starcoder2-3b widths, weights packed, 64
    rows over a 4096-page pool of 32: it compiles for the chip with the
    packed GEMM in it, and its temporaries stay far below one pool.  The
    stacked cache is updated in place; as scan outputs the new entries
    took a second pool-sized buffer (3.9 GB), which beside packed weights
    and a caller's bf16 copy no longer fit the chip's 16 GB."""
    import repro.kernels.ops as ops
    from repro.core import preset, use_fused_gemms
    from repro.models import (init_cache_paged, lm_decode_step, lm_init,
                              pack_serving_weights)
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    cfg, qcfg = get_config("starcoder2-3b", "full"), preset("e4m3_bf16act")
    params = jax.eval_shape(lambda: pack_serving_weights(
        lm_init(jax.random.PRNGKey(0), cfg), cfg, qcfg)[0])
    cache = jax.eval_shape(lambda: init_cache_paged(cfg, 64, 2048, 4096, 32))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)

    def step(p, c, tok, pos, pt):
        return lm_decode_step(p, c, tok, pos, cfg, qcfg, page_table=pt,
                              page_size=32)

    with use_fused_gemms(True):
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            on_chip(params), on_chip(cache),
            *on_chip((jax.ShapeDtypeStruct((64, 1), jnp.int32),
                      jax.ShapeDtypeStruct((64,), jnp.int32),
                      jax.ShapeDtypeStruct((64, 64), jnp.int32)))).compile()
    assert "%mx_matmul_pallas" in compiled.as_text()
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < pool / 8
