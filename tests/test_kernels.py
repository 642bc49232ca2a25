"""Per-kernel validation: shape/dtype sweep vs pure-jnp oracles (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import PSGConfig
from repro.kernels import ops, ref

SHAPES = [(64, 32, 48), (300, 130, 70), (512, 256, 128), (1024, 256, 256),
          (128, 7, 9)]


@pytest.mark.parametrize("N,din,dout", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_psg_grad_w_matches_oracle(N, din, dout, dtype):
    cfg = PSGConfig(enabled=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(N + din))
    x = (jax.random.normal(k1, (N, din)) * 0.5).astype(dtype)
    gy = (jax.random.normal(k2, (N, dout)) * 0.01).astype(dtype)
    xf, gf = x.astype(jnp.float32), gy.astype(jnp.float32)
    want = np.asarray(ref.psg_grad_w_oracle(xf, gf, cfg))
    got, fb = ops.psg_grad_w(xf, gf, cfg)
    got = np.asarray(got)
    # Semantics are identical up to float determinism: the jitted kernel
    # wrapper and the eager oracle may round a handful of x/s values onto
    # adjacent quantization codes (1-ulp jit/eager divergence), shifting
    # borderline entries across the tau confidence threshold.  That is only
    # *observable* where the predictor and full-product signs disagree —
    # so every mismatch must be such a genuinely ambiguous entry, and the
    # overall rate must be tiny.
    from repro.core.psg import msb_of, quantize
    g_msb = np.asarray((msb_of(xf, cfg.bits_x, cfg.bits_x_msb).T
                        @ msb_of(gf, cfg.bits_g, cfg.bits_g_msb))
                       .astype(jnp.float32))
    g_full = np.asarray((quantize(xf, cfg.bits_x).T
                         @ quantize(gf, cfg.bits_g)).astype(jnp.float32))
    ambiguous = np.sign(g_msb) != np.sign(g_full)
    mism = want != got
    assert not (mism & ~ambiguous).any(), \
        f"{(mism & ~ambiguous).sum()} mismatches at unambiguous entries"
    assert mism.mean() < 5e-3
    assert 0.0 <= float(fb) <= 1.0


# CIFAR geometry is never MXU-aligned: widths 16/32/64 give k*k*C reduction
# dims of 144/288/576 and dout of 16/32/64 — none a multiple of 128.  The
# kernel clamps its (BM, BN, BK) tiles to the operand extents and pads to
# the clamped grid; these pin that the padding is masked out of the result
# (exact oracle match, unpadded output shape) and that the fallback stats
# grid matches the executed-tile count.
CIFAR_TILE_SHAPES = [(2 * 32 * 32, 9 * 16, 16),   # stage-0 body, width 16
                     (2 * 16 * 16, 9 * 32, 32),   # stage-1 body, width 32
                     (2 * 8 * 8, 9 * 64, 64),     # stage-2 body, width 64
                     (2 * 16 * 16, 16, 32),       # 1x1 projection shortcut
                     (100, 145, 33)]              # nothing aligned at all


@pytest.mark.parametrize("N,din,dout", CIFAR_TILE_SHAPES)
def test_psg_grad_w_non_mxu_aligned_tiles(N, din, dout):
    cfg = PSGConfig(enabled=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(N + din + dout))
    x = jax.random.normal(k1, (N, din)) * 0.5
    gy = jax.random.normal(k2, (N, dout)) * 0.01
    got, fb = ops.psg_grad_w(x, gy, cfg)
    assert got.shape == (din, dout)              # padding cropped
    want = np.asarray(ref.psg_grad_w_ref(x, gy, cfg))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert 0.0 <= float(fb) <= 1.0


@pytest.mark.parametrize("N,din,dout", CIFAR_TILE_SHAPES[:3])
def test_psg_kernel_stats_grid_matches_executed_tiles(N, din, dout):
    """The select's per-tile fallback grid covers exactly the padded tile
    grid of the kernel's output — ceil(din/BM) x ceil(dout/BN) with clamped
    tiles — so its mean is the executed-tile fallback ratio that
    ``ops.psg_grad_w`` reports (DESIGN.md §Dispatch caveat)."""
    from repro.core.quant import quantize_int
    from repro.kernels.psg_matmul import (DEFAULT_BM, DEFAULT_BN,
                                          predictor_matmul_pallas, psg_select)
    cfg = PSGConfig(enabled=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (N, din))
    gy = jax.random.normal(k2, (N, dout)) * 0.01
    xm, _ = quantize_int(x, cfg.bits_x_msb)
    gm, _ = quantize_int(gy, cfg.bits_g_msb)
    xq, _ = quantize_int(x, cfg.bits_x)
    gq, _ = quantize_int(gy, cfg.bits_g)
    out, stats = psg_select(predictor_matmul_pallas(xm, gm),
                            predictor_matmul_pallas(xq, gq), cfg.beta)
    bm = min(DEFAULT_BM, din)
    bn = min(DEFAULT_BN, dout)
    assert stats.shape == (-(-din // bm), -(-dout // bn))
    assert out.shape == (din, dout)
    _, ratio = ops.psg_grad_w(x, gy, cfg)
    assert float(ratio) == float(np.mean(np.asarray(stats)))


@pytest.mark.parametrize("beta", [0.02, 0.05, 0.1, 0.3])
def test_psg_threshold_beta_sweep(beta):
    cfg = PSGConfig(enabled=True, beta=beta)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (256, 128))
    gy = jax.random.normal(k2, (256, 64)) * 0.1
    want = np.asarray(ref.psg_grad_w_oracle(x, gy, cfg))
    got, _ = ops.psg_grad_w(x, gy, cfg)
    assert (want == np.asarray(got)).mean() > 0.999


@pytest.mark.parametrize("shape", [(128, 256), (7, 300), (1000,), (4, 4, 64)])
@pytest.mark.parametrize("bits", [2, 4, 8, 10, 16])
def test_quantize_kernel_matches_oracle(shape, bits):
    x = jax.random.normal(jax.random.PRNGKey(bits), shape)
    got = ops.quantize(x, bits)
    want = ref.quantize_ref(x, bits)
    # same grid; 1-ulp differences allowed (jit vs eager fma ordering of q*s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-7)


def test_quantize_idempotent():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    q1 = ops.quantize(x, 8)
    q2 = ops.quantize(q1, 8)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), atol=1e-7)


def test_predictor_matmul_pallas_matches_oracle():
    from repro.kernels.psg_matmul import predictor_matmul_pallas
    from repro.core.psg import quantize_int
    cfg = PSGConfig(enabled=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (384, 192))
    gy = jax.random.normal(k2, (384, 96))
    xm, _ = quantize_int(x, cfg.bits_x_msb)
    gm, _ = quantize_int(gy, cfg.bits_g_msb)
    got = predictor_matmul_pallas(xm, gm)
    want = xm.astype(jnp.float32).T @ gm.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_psg_kernel_block_shape_sweep():
    """BlockSpec tiling must not change results."""
    from repro.kernels.psg_matmul import predictor_matmul_pallas, psg_select
    from repro.core.psg import quantize_int
    cfg = PSGConfig(enabled=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (256, 128))
    gy = jax.random.normal(k2, (256, 64))
    xm, _ = quantize_int(x, cfg.bits_x_msb)
    gm, _ = quantize_int(gy, cfg.bits_g_msb)
    xq, _ = quantize_int(x, cfg.bits_x)
    gq, _ = quantize_int(gy, cfg.bits_g)
    outs = []
    for bm, bn, bk in [(32, 32, 64), (64, 64, 128), (128, 64, 256)]:
        prods = [predictor_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk)
                 for a, b in ((xm, gm), (xq, gq))]
        out, _ = psg_select(*prods, cfg.beta, bm=bm, bn=bn)
        outs.append(np.asarray(out))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


FLASH_SHAPES = [(2, 256, 4, 2, 64, True), (1, 300, 8, 8, 32, True),
                (2, 128, 4, 4, 64, False), (1, 384, 6, 2, 128, True),
                (1, 64, 2, 1, 64, True)]


@pytest.mark.parametrize("B,S,nh,nkv,hd,causal", FLASH_SHAPES)
def test_flash_attention_matches_oracle(B, S, nh, nkv, hd, causal):
    from repro.kernels.flash_attn import flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S + nh), 3)
    q = jax.random.normal(k1, (B, S, nh, hd))
    k = jax.random.normal(k2, (B, S, nkv, hd))
    v = jax.random.normal(k3, (B, S, nkv, hd))
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    want = ref.flash_attention_oracle(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_block_sweep():
    from repro.kernels.flash_attn import flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(k1, (1, 256, 4, 64))
    k = jax.random.normal(k2, (1, 256, 2, 64))
    v = jax.random.normal(k3, (1, 256, 2, 64))
    want = ref.flash_attention_oracle(q, k, v, True)
    for bq, bk in [(32, 64), (64, 32), (128, 128), (256, 64)]:
        got = flash_attention(q, k, v, bq=bq, bk=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attn import flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (1, 128, 4, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(k2, (1, 128, 4, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(k3, (1, 128, 4, 64)).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, bq=64, bk=64)
    want = ref.flash_attention_oracle(q.astype(jnp.float32),
                                      k.astype(jnp.float32),
                                      v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-2)
