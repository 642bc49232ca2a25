"""Pallas TPU kernel and select for the Predictive Sign Gradient
weight-gradient.

This is what the training backward pass executes: the ``custom_vjp`` in
``core/psg.py`` routes every PSG weight gradient through
``kernels/dispatch.py`` (backend selection rules in DESIGN.md §Dispatch) to
``kernels/ops.psg_grad_w``, which runs :func:`predictor_matmul_pallas`
twice and :func:`psg_select` once; the per-tile fallback flags of the
select drive the measured energy accounting (``core/energy.py``).

Computes ``sign_psg(x^T g_y)`` for a weight matmul's backward pass with the
paper's Eq. (2) semantics, adapted to the TPU memory/compute hierarchy
(DESIGN.md §3.2):

* the MSB *predictor* product runs over narrow operands (4-bit / 10-bit
  codes carried in int8/int16 containers);
* the *full* product runs over the 8-bit / 16-bit codes with the same
  kernel;
* the select takes the predictor's sign where ``|g_msb| >= tau = beta *
  max|g_msb|`` and the full product's sign elsewhere.  Output values are
  identical to the element-level oracle; only the *energy accounting* is
  tile-granular: a (BM, BN) output tile with any entry below ``tau`` is
  charged the full product.

Grid/BlockSpec layout: grid = (din/BM, dout/BN, N/BK) with the reduction
axis innermost; a VMEM scratch accumulator carries partial sums across the
k-steps; the output is written on the last k-step.  Tile sizes default to
(128, 128, 512) — MXU-aligned (multiples of 128) and a VMEM working set of
BK*(BM+BN)*2B + BM*BN*4B ≈ 0.3 MB, far under the ~16 MB/core budget, which
leaves room for double-buffered pipelining of the HBM->VMEM streams.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512

# Codes reach 2^15 - 1 (16-bit output-grad grid), which a single bf16 MXU
# pass rounds: every in-kernel product runs at full f32 precision so the
# code products, and hence the signs, match the f32 oracle in ref.py.
CODE_PRECISION = jax.lax.Precision.HIGHEST


def _pred_kernel(xm_ref, gm_ref, out_ref, acc, *, n_k: int):
    """One (i, j) tile of a code product; the k-loop accumulates in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xm = xm_ref[...].astype(jnp.float32)
    gm = gm_ref[...].astype(jnp.float32)
    acc[...] += jnp.dot(xm.T, gm, preferred_element_type=jnp.float32,
                        precision=CODE_PRECISION)

    @pl.when(k == n_k - 1)
    def _finish():
        out_ref[...] = acc[...]


def pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def predictor_matmul_pallas(x_msb: jnp.ndarray, g_msb: jnp.ndarray,
                            *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                            bk: int = DEFAULT_BK,
                            interpret: bool = True) -> jnp.ndarray:
    """``x_msb^T @ g_msb`` over integer codes (fp32), tiled.

    Serves both PSG products: the MSB predictor codes and the full codes
    (int8/int16 containers).
    """
    N, din = x_msb.shape
    dout = g_msb.shape[1]
    bm_, bn_, bk_ = min(bm, din), min(bn, dout), min(bk, N)
    xm = pad_to(x_msb, bk_, bm_)
    gm = pad_to(g_msb, bk_, bn_)
    Np, dinp = xm.shape
    doutp = gm.shape[1]
    n_k = Np // bk_
    out = pl.pallas_call(
        functools.partial(_pred_kernel, n_k=n_k),
        grid=(dinp // bm_, doutp // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bk_, bm_), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dinp, doutp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
    )(xm, gm)
    return out[:din, :dout]


def psg_select(g_msb: jnp.ndarray, g_full: jnp.ndarray, beta: float,
               *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (2) select over the two code products (XLA, after the kernels).

    Returns ``(sign (din, dout) float32 in {-1, 0, +1}, tile_fallback
    (ceil(din/bm'), ceil(dout/bn')) bool)`` with ``bm' = min(bm, din)`` and
    ``bn' = min(bn, dout)``, the kernel's output tiles.  A tile needs the
    full product when any of its entries, padding included, lies below
    ``tau = beta * max|g_msb|``.  Both products are in code units: the sign
    is scale-invariant, and so is ``tau`` relative to ``g_msb``.
    """
    din, dout = g_msb.shape
    bm_, bn_ = min(bm, din), min(bn, dout)
    tau = beta * jnp.max(jnp.abs(g_msb))
    conf = jnp.abs(pad_to(g_msb, bm_, bn_)) >= tau
    n_i, n_j = conf.shape[0] // bm_, conf.shape[1] // bn_
    tile_fallback = jnp.logical_not(
        jnp.all(conf.reshape(n_i, bm_, n_j, bn_), axis=(1, 3)))
    sign = jnp.where(conf[:din, :dout], jnp.sign(g_msb), jnp.sign(g_full))
    return sign, tile_fallback
