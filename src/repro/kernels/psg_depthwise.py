"""Pallas TPU kernel for the PSG weight gradient of a 3x3 depthwise conv.

A depthwise conv's weight gradient is a per-channel reduction, not a
matmul: for tap ``t`` (0..8) and channel ``c``, over the ``M = B*H'*W'``
output pixels,

    dW[t, c] = sum_m P_t[m, c] * gy[m, c]

where ``P_t`` is the tap-``t`` window of the padded input.  The MXU has
nothing to contract, so ``kernels/psg_matmul.py`` cannot serve it; this
kernel runs both PSG products (Eq. 2) on the VPU instead.

Layout (DESIGN.md §Kernels):

* the taps arrive as a rank-3 ``(9, M, C)`` operand that XLA has already
  gathered and strided (:func:`depthwise_taps`): Mosaic refuses strided
  slices inside a kernel (the stride-2 conv gather of ``kernels/conv.py``
  does not compile), and three of MobileNetV2's depthwise convs have
  stride 2.  The rank-3 first operand is also what tells this kernel
  apart from the rank-2 PSG matmul kernel in a device trace;
* the operands are integer codes, made element-wise on the block input
  before the gather (int8 for the 4- and 8-bit activation codes, int16
  for the 10- and 16-bit gradient codes, ``kernels/ops._codes``), so the
  kernel needs no scales and the tap stacks cost one byte per entry;
* grid ``(C / BC, M / BM)`` with the row (reduction) axis innermost; each
  step reads one ``(9, BM, BC)`` block of both tap stacks and one
  ``(BM, BC)`` block of both gradient codes and adds, per tap, the
  products of the MSB predictor codes and of the full codes into two f32
  VMEM accumulators ``(9, 8, BC)`` (row groups of 8 fold onto the 8
  sublanes with vector adds; the 8 partial sums are added after the
  kernel).  Products of codes are exact in f32 (at most 127 x 32767);
* ``BC`` is 128 lanes where ``C`` is a multiple of 128, else the whole
  ``C`` in one channel tile (a block as wide as its array is legal, and
  MobileNetV2's 96, 144, 192, 576 and 960 channels then need no padding
  copy); rows are padded with zero codes to a whole number of ``BM``.

The Eq. (2) select (:func:`psg_depthwise_select`) runs in XLA after the
kernel, as ``psg_matmul.psg_select`` does: ``tau = beta * max|g_msb|`` is
a maximum over the kernel's whole output, so it cannot be an input of
the pass that makes that output.  Its fallback statistic is element
level: the share of ``(tap, channel)`` entries below ``tau``, reported as
``psg_dw_fallback_ratio`` (``core/psg.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TAPS = 9
LANE = 128
SUBLANE = 8
ROW_ALIGN = 32              # int8 blocks tile rows by 32
BLOCK_ELEMS = 1024 * LANE   # rows x lanes of one tap in one grid step
KERNEL_NAME = "psg_depthwise_grad_w"


def _windows(x: jnp.ndarray, stride: int):
    """The 9 windows ``(B, H', W', C)`` of a 3x3 SAME conv (padding 1) of
    NHWC ``x``, tap ``t = 3*i + j``, strided before the gather (a stride-2
    conv computes a quarter of the products, not full resolution
    sliced)."""
    B, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return [xp[:, i:i + H:stride, j:j + W:stride, :]
            for i in range(3) for j in range(3)]


def depthwise_conv(x: jnp.ndarray, w: jnp.ndarray, stride: int
                   ) -> jnp.ndarray:
    """3x3 SAME depthwise conv of NHWC ``x`` with a ``(9, C)`` weight, in
    XLA: the multiply-sum over the stacked windows."""
    stack = jnp.stack(_windows(x, stride), axis=-2)          # (B,H',W',9,C)
    return jnp.sum(stack * w[None, None, None], axis=-2)


def depthwise_taps(x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """The windows as the kernel's rank-3 operand: ``(9, B*H'*W', C)``."""
    return jnp.stack(_windows(x, stride)).reshape(TAPS, -1, x.shape[-1])


def row_tile(m: int, lanes: int) -> int:
    """Rows of one grid step: a power of two that keeps one tap of a block
    near ``BLOCK_ELEMS`` entries, at least ``ROW_ALIGN``, and no larger
    than ``m`` rounded up to ``ROW_ALIGN``."""
    bm = ROW_ALIGN
    while bm * 2 * lanes <= BLOCK_ELEMS:
        bm *= 2
    return min(bm, -(-m // ROW_ALIGN) * ROW_ALIGN)


def _dw_kernel(xm_ref, gm_ref, xq_ref, gq_ref, msb_ref, full_ref, acc_msb,
               acc_full, *, n_i: int):
    """One (channel tile, row tile) step: both code products of every tap
    accumulated in one pass over the block."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_msb[...] = jnp.zeros_like(acc_msb)
        acc_full[...] = jnp.zeros_like(acc_full)

    gm = gm_ref[...].astype(jnp.float32)
    gq = gq_ref[...].astype(jnp.float32)
    bm, bc = gm.shape
    for t in range(TAPS):
        pm = xm_ref[t].astype(jnp.float32) * gm
        pf = xq_ref[t].astype(jnp.float32) * gq
        acc_msb[t] += pm.reshape(bm // SUBLANE, SUBLANE, bc).sum(axis=0)
        acc_full[t] += pf.reshape(bm // SUBLANE, SUBLANE, bc).sum(axis=0)

    @pl.when(i == n_i - 1)
    def _finish():
        msb_ref[...] = acc_msb[...]
        full_ref[...] = acc_full[...]


def psg_depthwise_products(xm: jnp.ndarray, gm: jnp.ndarray,
                           xq: jnp.ndarray, gq: jnp.ndarray, *,
                           bm: int | None = None, interpret: bool = True
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Both PSG code products of a depthwise weight gradient.

    ``xm``/``xq``: ``(9, M, C)`` MSB and full tap codes; ``gm``/``gq``:
    ``(M, C)`` MSB and full output-gradient codes.  Returns ``(g_msb,
    g_full)``, each ``(9, C)`` float32 in code units.  ``bm`` overrides
    the row tile (tests)."""
    _, M, C = xm.shape
    bc = LANE if C % LANE == 0 else C
    lanes = -(-bc // LANE) * LANE
    bm_ = row_tile(M, lanes) if bm is None else bm
    pad = (-M) % bm_
    if pad:
        xm, xq = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xm, xq))
        gm, gq = (jnp.pad(a, ((0, pad), (0, 0))) for a in (gm, gq))
    n_j, n_i = C // bc, (M + pad) // bm_
    taps = pl.BlockSpec((TAPS, bm_, bc), lambda j, i: (0, i, j))
    rows = pl.BlockSpec((bm_, bc), lambda j, i: (i, j))
    out = pl.BlockSpec((TAPS, SUBLANE, bc), lambda j, i: (0, 0, j))
    shape = jax.ShapeDtypeStruct((TAPS, SUBLANE, C), jnp.float32)
    msb, full = pl.pallas_call(
        functools.partial(_dw_kernel, n_i=n_i),
        grid=(n_j, n_i),
        in_specs=[taps, rows, taps, rows],
        out_specs=[out, out],
        out_shape=[shape, shape],
        scratch_shapes=[pltpu.VMEM((TAPS, SUBLANE, bc), jnp.float32),
                        pltpu.VMEM((TAPS, SUBLANE, bc), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(xm, gm, xq, gq)
    return msb.sum(axis=1), full.sum(axis=1)


def psg_depthwise_select(g_msb: jnp.ndarray, g_full: jnp.ndarray,
                         beta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (2) per entry: ``sign(g_msb)`` where ``|g_msb| >= tau = beta *
    max|g_msb|``, else ``sign(g_full)``.  Returns ``(sign (9, C) float32
    in {-1, 0, +1}, fallback)``: the share of entries below ``tau``."""
    tau = beta * jnp.max(jnp.abs(g_msb))
    conf = jnp.abs(g_msb) >= tau
    sign = jnp.where(conf, jnp.sign(g_msb), jnp.sign(g_full))
    return sign, jnp.mean(jnp.logical_not(conf).astype(jnp.float32))
