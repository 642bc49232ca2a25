"""jit'd wrappers over the Pallas kernels — the raw kernel entry points.

``psg_grad_w(x, gy, cfg)`` is the drop-in tile-level replacement for the
element-level ``repro.kernels.ref.psg_grad_w_ref`` oracle; outputs are
value-identical (the tile granularity only changes the *energy accounting*,
reported via the returned fallback-tile ratio).

Backend selection (reference vs. Pallas-interpret vs. Mosaic-compiled) is
owned by ``repro.kernels.dispatch`` — model and training code should call
the dispatch layer, not this module (DESIGN.md §Dispatch).  The ``interpret``
flag here is a plain argument: on this CPU container the dispatch layer
passes ``True`` (kernel body executed by the Pallas interpreter); on a real
TPU it resolves to ``False`` and the kernels lower through Mosaic.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import PSGConfig
from repro.core.quant import qscale
from repro.kernels import conv as _cv
from repro.kernels import flash_attn as _fa
from repro.kernels import psg_depthwise as _dw
from repro.kernels import psg_matmul as _pm
from repro.kernels import quant as _q


def _codes(x: jnp.ndarray, bits: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Integer codes on the ``bits``-bit grid + the grid scale."""
    s = qscale(x, bits)
    lim = 2.0 ** (bits - 1) - 1.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -lim, lim)
    dt = jnp.int8 if bits <= 8 else jnp.int16
    return q.astype(dt), s


@partial(jax.jit, static_argnames=("cfg", "interpret", "mesh", "axes"))
def psg_grad_w(x2: jnp.ndarray, gy2: jnp.ndarray, cfg: PSGConfig,
               interpret: bool = True, mesh=None, axes: Tuple[str, ...] = ()
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tile-level PSG weight gradient.

    Two code products in the Pallas kernel (MSB predictor codes, full
    codes), then the Eq. (2) select (``psg_matmul.psg_select``).  Returns
    (sign (din,dout) float32 in {-1,0,+1}, fallback_tile_ratio scalar — the
    fraction of output tiles that needed the full product; the energy model
    charges full-precision MACs only for those).

    With mesh ``axes`` (data parallelism) the rows of ``x2``/``gy2`` are
    split over them: a Pallas kernel cannot be partitioned by the compiler,
    so each shard runs the products on its own rows inside ``shard_map``
    and the partial products are summed across shards.  The quantization
    scales, the threshold and the select see the global batch, as on one
    device; only the f32 summation order differs.
    """
    codes = (_codes(x2, cfg.bits_x_msb)[0], _codes(gy2, cfg.bits_g_msb)[0],
             _codes(x2, cfg.bits_x)[0], _codes(gy2, cfg.bits_g)[0])
    prod = partial(_pm.predictor_matmul_pallas, interpret=interpret)

    def products(xm, gm, xq, gq):
        return prod(xm, gm), prod(xq, gq)

    def summed(*c):
        return tuple(jax.lax.psum(p, axes) for p in products(*c))

    if axes:
        prods = jax.shard_map(summed, mesh=mesh,
                              in_specs=(P(axes, None),) * 4,
                              out_specs=(P(), P()), check_vma=False)(*codes)
    else:
        prods = products(*codes)
    sign, tiles = _pm.psg_select(*prods, cfg.beta)
    return sign, jnp.mean(tiles.astype(jnp.float32))


@partial(jax.jit, static_argnames=("cfg", "stride", "interpret"))
def psg_depthwise_grad_w(x: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                         stride: int, interpret: bool = True
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PSG weight gradient of a 3x3 depthwise conv.

    ``x``: the conv's NHWC input ``(B, H, W, C)``; ``gy``: its output
    gradient ``(B, H', W', C)``.  The codes are made element-wise on
    ``x`` and ``gy`` (per-tensor scales, as :func:`psg_grad_w` makes
    them), the tap stacks are gathered from the codes in XLA, and both
    code products run in one pass of the kernel
    (``kernels/psg_depthwise.py``).  Returns ``(sign (9, C) float32 in
    {-1, 0, +1}, fallback)``: the share of ``(tap, channel)`` entries
    below the predictor's threshold.
    """
    C = x.shape[-1]
    g2 = gy.reshape(-1, C)
    taps = [_dw.depthwise_taps(_codes(x, b)[0], stride)
            for b in (cfg.bits_x_msb, cfg.bits_x)]
    g_msb, g_full = _dw.psg_depthwise_products(
        taps[0], _codes(g2, cfg.bits_g_msb)[0], taps[1],
        _codes(g2, cfg.bits_g)[0], interpret=interpret)
    return _dw.psg_depthwise_select(g_msb, g_full, cfg.beta)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize(x: jnp.ndarray, bits: int, interpret: bool = True
             ) -> jnp.ndarray:
    return _q.quantize_pallas(x, bits, interpret=interpret)


@partial(jax.jit, static_argnames=("k", "stride", "interpret"))
def conv_fwd(xq: jnp.ndarray, wq: jnp.ndarray, k: int, stride: int,
             interpret: bool = True) -> jnp.ndarray:
    """Implicit-GEMM conv forward on pre-quantized operands.

    ``xq``: pre-padded NHWC ``(B, Hp, Wp, C)``; ``wq``: patch-major
    ``(k*k*C, dout)``.  Value-equal to the materialized
    ``kernels/ref.conv_fwd_ref`` up to fp32 tap-summation order — the
    patch tensor is never written to HBM.
    """
    return _cv.conv_fwd_pallas(xq, wq, k=k, stride=stride,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("k", "stride", "hp", "wp", "interpret"))
def conv_grad_x(gq: jnp.ndarray, wq: jnp.ndarray, k: int, stride: int,
                hp: int, wp: int, interpret: bool = True) -> jnp.ndarray:
    """Implicit transposed-conv input gradient on pre-quantized operands.

    ``gq``: quantized output-grad ``(B, Ho, Wo, dout)``; ``wq``:
    patch-major quantized weight; ``hp``/``wp``: the pre-padded input
    extent.  Returns ``dx (B, hp, wp, C)`` float32 — value-equal to the
    col2im reference (``kernels/ref.conv_grad_x_ref``) up to fp32
    tap-summation order; no dpatches tensor, no k^2 scatter passes.
    """
    return _cv.conv_grad_x_pallas(gq, wq, k=k, stride=stride, hp=hp, wp=wp,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("cfg", "k", "stride", "interpret"))
def conv_grad_w(xp: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                k: int, stride: int, interpret: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tile-level PSG conv weight gradient, implicit im2col gather.

    ``xp``: pre-padded NHWC input (raw values; codes are built here the
    same way :func:`psg_grad_w` builds them — element-wise on the padded
    input, which carries the identical quantization grid as the patch
    tensor since gathering commutes with the per-tensor code map).
    Returns ``(sign (k*k*C, dout) float32 patch-major, fallback_tile_ratio
    scalar)`` — the same contract as :func:`psg_grad_w` on the
    materialized operand.
    """
    xm_c, _ = _codes(xp, cfg.bits_x_msb)
    gm_c, _ = _codes(gy, cfg.bits_g_msb)
    xq_c, _ = _codes(xp, cfg.bits_x)
    gq_c, _ = _codes(gy, cfg.bits_g)
    # pass 1: predictor product for the adaptive threshold (code units —
    # sign(g) is scale-invariant, exactly as in psg_grad_w above)
    g_msb = _cv.conv_grad_w_predictor_pallas(xm_c, gm_c, k=k, stride=stride,
                                             interpret=interpret)
    tau_codes = cfg.beta * jnp.max(jnp.abs(g_msb))
    sign_i8, stats = _cv.conv_grad_w_pallas(
        xm_c, gm_c, xq_c, gq_c, tau_codes, k=k, stride=stride,
        interpret=interpret)
    return sign_i8.astype(jnp.float32), jnp.mean(stats.astype(jnp.float32))


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, interpret: bool = True
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flash attention forward + the logsumexp residual.

    q: (B, S, nh, hd); k/v: (B, T, nkv, hd).  Returns (o, lse) with
    lse (B, nh, S) fp32 — the only extra residual the recomputed-tile
    backward needs; no (S, T) tensor touches HBM.
    """
    return _fa.flash_attention(q, k, v, causal=causal, interpret=interpret,
                               return_lse=True)


@partial(jax.jit, static_argnames=("cfg", "causal", "interpret"))
def flash_attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        o: jnp.ndarray, lse: jnp.ndarray, do: jnp.ndarray,
                        cfg: PSGConfig, causal: bool = True,
                        interpret: bool = True):
    """PSG flash-attention backward: (dq, dk, dv, fallback_tile_ratio).

    dq comes from the plain fp32 recompute kernel.  dk/dv come from the
    dual-accumulator PSG kernel: per-query-head MSB and full code
    products, group-summed here to kv heads, then the Eq. (2) select
    (predictor value where ``|g_msb| >= beta*max|g_msb|``, dequantized
    full product elsewhere) — the finish stage hoisted out of the kernel
    because a Pallas grid step cannot reduce across query heads (see
    flash_attn.py's GQA note).  The fallback ratio counts (bk x hd)
    kv-tiles of the dk/dv outputs that contain any fallback element —
    the tile granularity the energy model charges full-precision MACs at.
    """
    B, S, nh, hd = q.shape
    T, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    do32 = do.astype(jnp.float32)
    delta = jnp.einsum("bsnh,bsnh->bns", do32, o.astype(jnp.float32))
    dq = _fa.flash_bwd_dq_pallas(q, k, v, do, lse, delta, causal=causal,
                                 interpret=interpret)
    scales = _fa.attention_psg_scales(
        q, v, do, delta, bits_x=cfg.bits_x, bits_x_msb=cfg.bits_x_msb,
        bits_g=cfg.bits_g, bits_g_msb=cfg.bits_g_msb)
    lims = (_fa.qlim(cfg.bits_x), _fa.qlim(cfg.bits_x_msb),
            _fa.qlim(cfg.bits_g), _fa.qlim(cfg.bits_g_msb))
    parts = _fa.flash_bwd_dkv_pallas(q, k, v, do, lse, delta, scales,
                                     lims=lims, causal=causal,
                                     interpret=interpret)
    # group-sum the per-query-head code products to kv heads (identical
    # jnp.sum in the oracle keeps the products bit-aligned)
    dv_m, dv_f, dk_m, dk_f = (
        p.reshape(B, T, nkv, g, hd).sum(axis=3) for p in parts)
    s_q, s_qm, s_do, s_dom, s_ds, s_dsm = scales
    lim_x, lim_xm = lims[0], lims[1]
    dv, r_dv = _fa.psg_attention_select(dv_m, dv_f, (1.0 / lim_xm) * s_dom,
                                        (1.0 / lim_x) * s_do, cfg.beta)
    dk, r_dk = _fa.psg_attention_select(dk_m, dk_f, s_dsm * s_qm,
                                        s_ds * s_q, cfg.beta)
    return dq, dk, dv, 0.5 * (r_dv + r_dk)
