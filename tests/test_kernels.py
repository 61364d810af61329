"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (E2M1, E2M3, E3M2, E4M3, E5M2, QuantConfig, preset,
                        quantize_mx, use_fused_gemms)
from repro.kernels import (mx_matmul, mx_matmul_dgrad, mx_matmul_dgrad_ref,
                           mx_matmul_ref, mx_matmul_wgrad,
                           mx_matmul_wgrad_ref, mx_quantize, mx_quantize_ref)

FMTS = [E4M3, E5M2, E2M3, E3M2, E2M1]
RNG = np.random.RandomState(42)


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", [(1, 32), (4, 64), (64, 128), (3, 5, 96),
                                   (7, 33)],
                         ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_quant_kernel_matches_ref(fmt, shape, dtype):
    x = (jnp.asarray(RNG.randn(*shape).astype(np.float32)) * 5).astype(dtype)
    y_k = mx_quantize(x, fmt, axis=-1)
    y_r = mx_quantize_ref(x, fmt, axis=-1)
    np.testing.assert_array_equal(np.asarray(y_k, np.float32),
                                  np.asarray(y_r, np.float32))


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
def test_quant_kernel_axis0(fmt):
    x = jnp.asarray(RNG.randn(64, 48).astype(np.float32))
    y_k = mx_quantize(x, fmt, axis=0)
    y_r = mx_quantize_ref(x, fmt, axis=0)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_quant_kernel_wide_rows_shrink_the_row_tile():
    """Rows too wide for a 256-row block in VMEM get a shorter row tile
    (here 16 rows, so 24 rows pad to two tiles); same bits."""
    from repro.kernels.mx_quant import _TILE_ELEMS
    k = _TILE_ELEMS // 16
    rng = np.random.RandomState(7)   # leave RNG's stream to the other tests
    x = jnp.asarray(rng.randn(24, k).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(mx_quantize(x, E4M3)),
                                  np.asarray(mx_quantize_ref(x, E4M3)))


@pytest.mark.parametrize("mkn", [(32, 32, 32), (64, 128, 32), (128, 256, 64),
                                 (16, 96, 48), (100, 160, 72)], ids=str)
@pytest.mark.parametrize("fa,fb", [(E4M3, E4M3), (E5M2, E4M3), (None, E2M3),
                                   (E2M1, None)],
                         ids=lambda f: getattr(f, "name", "bf16"))
def test_matmul_kernel_matches_ref(mkn, fa, fb):
    m, k, n = mkn
    a = jnp.asarray(RNG.randn(m, k).astype(np.float32))
    b = jnp.asarray(RNG.randn(k, n).astype(np.float32))
    y_k = mx_matmul(a, b, fa, fb)
    y_r = mx_matmul_ref(a, b, fa, fb)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-6, atol=1e-5)


def test_matmul_kernel_batched_lhs():
    a = jnp.asarray(RNG.randn(2, 8, 64).astype(np.float32))
    b = jnp.asarray(RNG.randn(64, 32).astype(np.float32))
    y = mx_matmul(a, b, E4M3, E4M3)
    assert y.shape == (2, 8, 32)
    y_r = mx_matmul_ref(a.reshape(16, 64), b, E4M3, E4M3).reshape(2, 8, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), rtol=1e-6)


def test_matmul_zero_padding_blocks_are_inert():
    """Padding K to tile multiples adds all-zero MX blocks: result equals
    the unpadded oracle exactly."""
    a = jnp.asarray(RNG.randn(40, 160).astype(np.float32))
    b = jnp.asarray(RNG.randn(160, 24).astype(np.float32))
    y_k = mx_matmul(a, b, E4M3, E4M3)   # tiles force padding on M/N
    y_r = mx_matmul_ref(a, b, E4M3, E4M3)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-6)


# ---------------------------------------------------------------------------
# Weights packed at rest (MXWeight): pack/unpack and dequantize-on-load
# ---------------------------------------------------------------------------
def _edge_weights(shape, seed=3):
    """Weights whose 32-blocks along K span 2^-12..2^12 of scale, with an
    all-zero block, a block of signed zeros, a block whose max clamps to
    448 (E4M3) and a block whose small values land on subnormal codes."""
    rng = np.random.RandomState(seed)
    *lead, k, n = shape
    w = rng.randn(*shape).astype(np.float32)
    w *= np.exp2(rng.randint(-12, 13, size=(*lead, k // 32, 1, n))
                 ).repeat(32, axis=-2).reshape(shape)
    w[..., :32, 0] = 0.0
    w[..., 32:64, 1] = -0.0
    w[..., :32, 2] = 1.0
    w[..., 0, 2] = 479.0 / 256.0            # 479/256 * 256 rounds past 448
    # scale 2^-8 (max 1.5), so E4M3's subnormal step 2^-9 is 2^-17 here;
    # odd multiples of 2^-18 sit half-way between two codes
    w[..., 32:64, 3] = -np.arange(32) % 16 * np.float32(2.0 ** -18)
    w[..., 32, 3] = 1.5
    return w


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [(96, 160), (3, 64, 256)], ids=str)
def test_pack_unpack_round_trip_is_quantize_mx(fmt, dtype, shape):
    """unpack(pack(W)) is quantize_mx(W, axis=-2) bit for bit, zero blocks,
    subnormal codes, the clamp and signed zeros included; a stacked
    leading axis packs layer by layer."""
    from repro.core.mx import pack_mx, unpack_mx
    w = jnp.asarray(_edge_weights(shape)).astype(dtype)
    mw = pack_mx(w, fmt)
    assert mw.codes.dtype == jnp.uint8 and mw.codes.shape == w.shape
    assert mw.exps.dtype == jnp.uint8
    assert mw.exps.shape == w.shape[:-2] + (w.shape[-2] // 32, w.shape[-1])
    got = np.asarray(unpack_mx(mw, dtype))
    want = np.asarray(quantize_mx(w, fmt, axis=-2))
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    if fmt is E4M3:
        codes = np.asarray(mw.codes)
        assert (codes[..., 0, 2] == 0x7E).all()          # 448: S.1111.110
        assert ((codes[..., 32:64, 3] & 0x78) == 0).any()  # subnormal codes
        assert (codes[..., 32:64, 1] == 0).all()         # -0 packs as +0


@pytest.mark.parametrize("m", [1, 64, 256])
@pytest.mark.parametrize("k,n,tiles", [
    (256, 256, None),                  # wk / wv: N = 256, one tile
    (384, 1536, None),                 # wq / w_up: N in 512-wide tiles
    (2048, 384, (64, 128, 1024)),      # w_down: K over two K tiles
    (96, 160, None),                   # N padded to a lane tile
    (1056, 160, (64, 128, 256)),       # K and N padded to given tiles
], ids=str)
@pytest.mark.parametrize("fa", [None, E4M3], ids=["bf16", "e4m3"])
def test_packed_matmul_bit_identical_to_quantize_on_load(m, k, n, tiles, fa):
    """The dequantize-on-load path of mx_matmul_pallas (an MXWeight b)
    equals its quantize-on-load path (bf16 b) bit for bit, at the same
    tiles: the packed path's own tiles, or given ones.  The packed path
    pads M to whole 16-row tiles and N to whole tiles, so the
    quantize-on-load path gets the same zero rows and columns (it would
    otherwise shrink its tiles to M and N, and XLA:CPU sums dots of other
    shapes in other orders; at M = 1 a vector-matrix dot fused with the
    quantizer)."""
    from repro.core.mx import pack_mx
    from repro.kernels.mx_matmul import _packed_tiles, mx_matmul_pallas
    rng = np.random.RandomState(m + k + n)
    a = jnp.asarray(rng.randn(m, k).astype(np.float32)).astype(jnp.bfloat16)
    w = jnp.asarray(_edge_weights((k, n)) / 64).astype(jnp.bfloat16)
    tm, tn, tk = tiles or _packed_tiles(m, k, n, 32, fa is not None)
    want = mx_matmul_pallas(jnp.pad(a, ((0, -m % tm), (0, 0))),
                            jnp.pad(w, ((0, 0), (0, -n % tn))), fa, E4M3,
                            tile_m=tm, tile_n=tn, tile_k=tk,
                            interpret=True)[:m, :n]
    got = mx_matmul_pallas(
        a, pack_mx(w, E4M3), fa, None, interpret=True,
        **({} if tiles is None else dict(tile_m=tm, tile_n=tn, tile_k=tk)))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_packed_dense_contract_dispatch():
    """mx_contract(kind="dense") given an MXWeight: the emulation
    (Q(x) @ unpack(w)) and the fused path (the kernel, interpret mode)
    give what the bf16 weight gives on the emulation path, bit for bit."""
    from repro.core import mx_contract
    from repro.core.mx import pack_mx
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 8, 256).astype(np.float32)
                    ).astype(jnp.bfloat16)
    w = jnp.asarray(_edge_weights((256, 128)) / 16).astype(jnp.bfloat16)
    for name in ("e4m3_bf16act", "e4m3_fwd_only"):
        cfg = preset(name)
        mw = pack_mx(w, cfg.w_fwd)
        want = mx_contract(x, w, cfg, kind="dense")
        got = mx_contract(x, mw, cfg, kind="dense")
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        with use_fused_gemms(True):
            fused = mx_contract(x, mw, cfg, kind="dense")
        np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                      np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# Backward kernels: dgrad (blocks along N) and wgrad (blocks along T)
# ---------------------------------------------------------------------------
BWD_FMTS = [(E4M3, E4M3), (E5M2, E5M2), (E2M1, E2M1), (E5M2, E4M3),
            (None, E4M3), (E5M2, None)]
BWD_IDS = ["-".join(getattr(f, "name", "bf16") for f in p) for p in BWD_FMTS]


@pytest.mark.parametrize("mkn", [(16, 48, 64), (128, 128, 256), (8, 100, 32),
                                 (3, 40, 96), (130, 72, 160)], ids=str)
@pytest.mark.parametrize("fg,fw", BWD_FMTS, ids=BWD_IDS)
def test_dgrad_kernel_bit_identical_to_ref(mkn, fg, fw):
    """Single-contraction-tile dgrad shapes are *bit-identical* to the
    oracle (same quantized values, same fp32 accumulation order)."""
    m, k, n = mkn
    dy = jnp.asarray(RNG.randn(m, n).astype(np.float32))
    w = jnp.asarray(RNG.randn(k, n).astype(np.float32))
    y_k = mx_matmul_dgrad(dy, w, fg, fw)
    y_r = mx_matmul_dgrad_ref(dy, w, fg, fw)
    assert y_k.shape == (m, k)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("tkn", [(48, 16, 64), (256, 128, 128), (96, 100, 24),
                                 (160, 40, 72), (64, 3, 96)], ids=str)
@pytest.mark.parametrize("fa,fg", BWD_FMTS, ids=BWD_IDS)
def test_wgrad_kernel_bit_identical_to_ref(tkn, fa, fg):
    t, k, n = tkn
    x = jnp.asarray(RNG.randn(t, k).astype(np.float32))
    dy = jnp.asarray(RNG.randn(t, n).astype(np.float32))
    y_k = mx_matmul_wgrad(x, dy, fa, fg)
    y_r = mx_matmul_wgrad_ref(x, dy, fa, fg)
    assert y_k.shape == (k, n)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_bwd_kernels_multitile_contraction():
    """Contraction longer than one tile: accumulation splits across grid
    steps, so agreement is up to fp32 summation order only."""
    dy = jnp.asarray(RNG.randn(64, 512).astype(np.float32))
    w = jnp.asarray(RNG.randn(96, 512).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(mx_matmul_dgrad(dy, w, E5M2, E4M3)),
        np.asarray(mx_matmul_dgrad_ref(dy, w, E5M2, E4M3)),
        rtol=1e-6, atol=1e-5)
    x = jnp.asarray(RNG.randn(512, 96).astype(np.float32))
    d = jnp.asarray(RNG.randn(512, 64).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(mx_matmul_wgrad(x, d, E4M3, E5M2)),
        np.asarray(mx_matmul_wgrad_ref(x, d, E4M3, E5M2)),
        rtol=1e-6, atol=1e-5)


def test_bwd_kernels_non_block_contraction_falls_back():
    """Contraction axis not a multiple of the MX block routes to the jnp
    oracle (same numerics, no kernel constraint violated)."""
    dy = jnp.asarray(RNG.randn(8, 40).astype(np.float32))   # N=40, 40%32!=0
    w = jnp.asarray(RNG.randn(16, 40).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(mx_matmul_dgrad(dy, w, E4M3, E4M3)),
        np.asarray(mx_matmul_dgrad_ref(dy, w, E4M3, E4M3)))
    x = jnp.asarray(RNG.randn(40, 16).astype(np.float32))   # T=40
    d = jnp.asarray(RNG.randn(40, 8).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(mx_matmul_wgrad(x, d, E4M3, E4M3)),
        np.asarray(mx_matmul_wgrad_ref(x, d, E4M3, E4M3)))


def test_dgrad_kernel_batched_lhs():
    dy = jnp.asarray(RNG.randn(2, 8, 64).astype(np.float32))
    w = jnp.asarray(RNG.randn(48, 64).astype(np.float32))
    y = mx_matmul_dgrad(dy, w, E4M3, E4M3)
    assert y.shape == (2, 8, 48)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(mx_matmul_dgrad_ref(dy, w, E4M3, E4M3)))


@pytest.mark.tpu
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled (non-interpret) kernels need a TPU")
def test_kernels_compiled_on_tpu_match_ref():
    """On real hardware the Mosaic-compiled kernels must agree with the
    oracle to fp32-accumulation-order tolerance."""
    dy = jnp.asarray(RNG.randn(256, 512).astype(np.float32))
    w = jnp.asarray(RNG.randn(384, 512).astype(np.float32))
    x = jnp.asarray(RNG.randn(512, 384).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(mx_matmul_dgrad(dy, w, E5M2, E4M3)),
        np.asarray(mx_matmul_dgrad_ref(dy, w, E5M2, E4M3)),
        rtol=1e-5, atol=1e-4)
    # wgrad contracts the token axis: x (512, 384) against dy^T (512, 256)
    np.testing.assert_allclose(
        np.asarray(mx_matmul_wgrad(x, dy.T, E4M3, E5M2)),
        np.asarray(mx_matmul_wgrad_ref(x, dy.T, E4M3, E5M2)),
        rtol=1e-5, atol=1e-4)


@pytest.mark.tpu
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled (non-interpret) kernels need a TPU")
@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
def test_kernels_compiled_on_tpu_match_ref_at_olmo_widths(fmt):
    """At olmo-paper widths (4096 tokens, d_model 512, d_ff 2048, 8 heads
    of 64, context 512) on the chip: the quantizer — whose per-32-lane
    block max is a butterfly of lane rotations — equals the oracle
    bitwise, so its rotations run the way interpret mode runs them; the
    forward GEMM agrees to fp32-accumulation-order tolerance; and the
    flash forward agrees as the any-shape property in test_properties.py
    asks (tight logsumexp, a 1-mantissa-step budget on the output)."""
    from repro.configs import get_config
    from repro.kernels import mx_flash_attention, mx_flash_attention_ref
    rng = np.random.RandomState(11)
    for shape in [(4096, 512), (4096, 2048)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 5
                        ).astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(mx_quantize(x, fmt), np.float32),
            np.asarray(mx_quantize_ref(x, fmt), np.float32))
    a = jnp.asarray(rng.randn(4096, 512).astype(np.float32))
    b = jnp.asarray(rng.randn(512, 2048).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(mx_matmul(a, b, fmt, fmt)),
        np.asarray(mx_matmul_ref(a, b, fmt, fmt)), rtol=1e-5, atol=1e-4)
    spec = get_config("olmo-paper", "full").attn_spec()
    q = jnp.asarray(rng.randn(16, 1, 512, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(16, 512, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(16, 512, 64).astype(np.float32))
    o_k, l_k = mx_flash_attention(q, k, v, fmt, spec)
    o_r, l_r = jax.jit(mx_flash_attention_ref,
                       static_argnames=("fmt", "spec"))(q, k, v, fmt, spec)
    o_k, l_k, o_r, l_r = (np.asarray(t) for t in (o_k, l_k, o_r, l_r))
    np.testing.assert_allclose(l_k, l_r, rtol=3e-7, atol=1e-5)
    assert np.linalg.norm(o_k - o_r) / np.linalg.norm(o_r) < 0.05


# ---------------------------------------------------------------------------
# Custom-VJP QLinear end-to-end through the fused kernels (interpret mode)
# ---------------------------------------------------------------------------
def test_dense_contract_vjp_plumbing_check_grads():
    """With quantization off, the custom VJP must match numerical grads
    (jax.test_util.check_grads semantics) — validates the VJP wiring that
    the quantized paths share.  (An unquantized config never dispatches to
    the kernels; fused-path gradient coverage is
    test_qlinear_fused_step_matches_emulation below.)"""
    from jax.test_util import check_grads
    from repro.core import mx_contract
    x = jnp.asarray(RNG.randn(8, 64).astype(np.float32))
    w = jnp.asarray(RNG.randn(64, 32).astype(np.float32) * 0.1)
    cfg = QuantConfig.bf16()
    check_grads(lambda a, b: mx_contract(a, b, cfg, kind="dense"), (x, w),
                order=1, modes=["rev"], rtol=2e-3)


@pytest.mark.parametrize("preset_name", ["mxfp8_e4m3", "mx_mix"])
def test_qlinear_fused_step_matches_emulation(preset_name):
    """A full fwd+bwd through a norm->MLP->norm stack: grads from the fused
    Pallas path (interpret mode) are bit-identical to the emulation path —
    all three GEMMs of the step route through the kernels per QuantConfig."""
    from repro.models.layers import apply_norm, norm_init
    from repro.models.mlp import mlp_apply, mlp_init
    cfg = preset(preset_name)
    key = jax.random.PRNGKey(0)
    params = {"ln": norm_init(64), "mlp": mlp_init(key, 64, 128, "swiglu")}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 64))

    def loss(p, xx):
        h = apply_norm(p["ln"], xx, cfg)
        return jnp.sum(jnp.square(mlp_apply(p["mlp"], h, cfg, "swiglu")))

    g_emul = jax.grad(loss)(params, x)
    with use_fused_gemms(True):
        g_fused = jax.grad(loss)(params, x)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), g_fused, g_emul)


# ---------------------------------------------------------------------------
# Flash-attention kernel family vs jnp oracle (bit-exact, interpret mode)
# ---------------------------------------------------------------------------
from repro.core import AttnSpec  # noqa: E402
from repro.kernels import (mx_attention_decode, mx_attention_decode_ref,  # noqa: E402
                           mx_flash_attention, mx_flash_attention_bwd,
                           mx_flash_attention_bwd_ref, mx_flash_attention_ref)

ATTN_SPECS = [
    AttnSpec.training(q_chunk=64, kv_chunk=64),
    AttnSpec.training(causal=False, q_chunk=64, kv_chunk=64),
    AttnSpec.training(window=48, q_chunk=64, kv_chunk=64),
]


def _attn_qkv(bh=2, g=2, tq=160, tk=160, d=64, dv=64, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(bh, g, tq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(bh, tk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(bh, tk, dv).astype(np.float32))
    do = jnp.asarray(rng.randn(bh, g, tq, dv).astype(np.float32))
    return q, k, v, do


@pytest.mark.parametrize("fmt", [None, E4M3], ids=["bf16", "e4m3"])
@pytest.mark.parametrize("spec", ATTN_SPECS, ids=lambda s: s.kind)
def test_attention_fwd_kernel_bit_identical_to_oracle(spec, fmt):
    """Tq=Tk=160 is not a tile multiple: the pad path is covered too."""
    q, k, v, _ = _attn_qkv()
    o_k, l_k = mx_flash_attention(q, k, v, fmt, spec)
    o_r, l_r = mx_flash_attention_ref(q, k, v, fmt, spec)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("fmt", [None, E4M3], ids=["bf16", "e4m3"])
@pytest.mark.parametrize("spec", ATTN_SPECS, ids=lambda s: s.kind)
def test_attention_dgrad_kernel_bit_identical_to_oracle(spec, fmt):
    q, k, v, do = _attn_qkv()
    out, lse = mx_flash_attention_ref(q, k, v, fmt, spec)
    g_k = mx_flash_attention_bwd(q, k, v, do, out, lse, fmt, spec)
    g_r = mx_flash_attention_bwd_ref(q, k, v, do, out, lse, fmt, spec)
    for a, b, name in zip(g_k, g_r, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_attention_kernel_rect_with_offset():
    """Tq != Tk with a query-position offset (the prefill-continuation
    shape): kernel must agree with the oracle bitwise."""
    spec = AttnSpec.training(q_chunk=64, kv_chunk=64, q_offset=64)
    q, k, v, do = _attn_qkv(tq=96, tk=160)
    o_k, l_k = mx_flash_attention(q, k, v, E4M3, spec)
    o_r, l_r = mx_flash_attention_ref(q, k, v, E4M3, spec)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))
    g_k = mx_flash_attention_bwd(q, k, v, do, o_r, l_r, E4M3, spec)
    g_r = mx_flash_attention_bwd_ref(q, k, v, do, o_r, l_r, E4M3, spec)
    for a, b in zip(g_k, g_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fmt", [None, E4M3], ids=["bf16", "e4m3"])
def test_attention_decode_kernel_bit_identical_to_oracle(fmt):
    q, k, v, _ = _attn_qkv(tk=160)
    qd = q[:, :, 0]
    valid = jnp.arange(160)[None, :] <= jnp.asarray([[80], [159]])
    o_k = mx_attention_decode(qd, k, v, valid, fmt)
    o_r = mx_attention_decode_ref(qd, k, v, valid, fmt)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))


def test_attention_kernel_non_block_head_dim_falls_back():
    """d=48 is not an MX-block multiple: the dispatch wrapper must fall
    back to the oracle rather than mis-tile the quantization."""
    spec = AttnSpec.training(q_chunk=64, kv_chunk=64)
    q, k, v, _ = _attn_qkv(d=48)
    o_k, l_k = mx_flash_attention(q, k, v, E4M3, spec)
    o_r, l_r = mx_flash_attention_ref(q, k, v, E4M3, spec)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("preset_name", ["mxfp8_e4m3", "bf16"])
def test_flash_attn_contract_fused_grads_match_emulation(preset_name):
    """Value AND grads of mx_contract(kind="flash_attn") are bit-identical
    between the fused kernel path and the emulation path — both sides of
    the custom VJP share the same oracle numerics."""
    from repro.core import mx_contract
    cfg = preset(preset_name) if preset_name != "bf16" else QuantConfig.bf16()
    spec = AttnSpec.training(q_chunk=64, kv_chunk=64)
    q, k, v, do = _attn_qkv(tq=96, tk=96)

    def loss(q, k, v):
        out = mx_contract(q, (k, v), cfg, kind="flash_attn", spec=spec)
        return jnp.sum(out * do)

    val_e, g_e = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    with use_fused_gemms(True):
        val_f, g_f = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(val_f), np.asarray(val_e))
    for a, b in zip(g_f, g_e):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
