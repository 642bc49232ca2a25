"""Pallas TPU kernels: fused implicit-GEMM convolution + PSG weight grad.

The CIFAR backbones (``models/resnet.py``) historically ran every conv as
*materialized* im2col: ``conv_general_dilated_patches`` writes a
``(B*Ho*Wo, k*k*C)`` patch tensor to HBM — a 9x copy of the input for 3x3
convs — before the GEMM ever runs, and the PSG backward re-reads that copy
twice more to build its quantization codes.  The paper's energy story is
dominated by exactly this kind of data movement (PAPERS.md, Yang et al.),
so these kernels do the *implicit* GEMM instead: the k x k patch gather
happens **inside the kernel**, tile by tile, on VMEM-resident input blocks
— the im2col operand never exists in HBM (DESIGN.md §Kernels).

Layout contract (external API): operands use the model's layouts —
NHWC activations and ``(k*k*C, Cout)`` weights in the **patch-major**
(channel-major: row index = ``c*k*k + ki*k + kj``) order that
``conv_general_dilated_patches`` produces and the checkpoints store.
Kernels internally work **tap-major** (row = ``(ki*k + kj)*C + c``): each
filter tap ``t`` gathers one strided window of the input block and
contracts it against one contiguous ``C``-row slice of the weight.  The
wrappers convert (pure transposes, fused by XLA).

Forward (``conv_fwd_pallas``): grid ``(B, dout/BN)``; each step holds one
padded image ``(Hp, Wp, C)`` and a ``(k*k*C, BN)`` weight block in VMEM and
accumulates ``sum_t gather_t(x) @ w_t`` over the unrolled tap loop — the
implicit-GEMM k-loop.  HBM traffic is the input read (once per dout tile)
plus the output write; no patch tensor.

PSG weight gradient (``conv_grad_w_pallas``): mirrors
``psg_matmul.py``'s MSB-predictor / tile-fallback structure — grid
``(dout/BN, B)`` with the batch (reduction) axis innermost, VMEM scratch
accumulators for the narrow-code predictor product and the full
fixed-point product carried across images, ``pl.when``-gated init/finish,
and the adaptive threshold ``tau = beta * max|g_msb|`` applied per output
tile on the last step.  A *tile* here is one ``(C, BN)`` block of ``dw``
(one filter tap x one dout block): the emitted per-tile fallback flags are
the measured energy-accounting stats that flow through the probe cotangent
into ``psg_fallback_ratio`` (DESIGN.md §Dispatch), exactly like the matmul
kernel's.

Input gradient (``conv_grad_x_pallas``): the implicit *transposed* conv —
the exact transpose of the forward's unrolled tap loop.  Grid ``(B,
dout/BN)`` with the dout (reduction) axis innermost; each step gathers the
contributing ``gy`` windows per filter tap from the VMEM-resident
output-grad block and contracts them against the tap's ``(C, BN)`` weight
slice.  Stride-2 is handled by *dilated-window indexing*: dx is
decomposed into its ``stride x stride`` spatial phases, each phase a
stride-1 window-gather conv over the (in-VMEM zero-padded) ``gy`` block —
no dilated gy tensor, no col2im scatter.  The phase results interleave
back via a pure stack+reshape, accumulate in an f32 VMEM tile across dout
tiles, and each dx block is written exactly once on the last reduction
step — versus the demoted col2im reference (``ref.conv_grad_x_ref``)
whose k^2 strided ``.at[].add`` passes read-modify-write a full-size HBM
accumulator once per tap.

VMEM budget: one image block ``Hp*Wp*C`` + two ``(k*k*C, BN)``
accumulators.  For every CIFAR ResNet / MobileNetV2 shape this is well
under 1 MB (worst: stage-0 ResNet ``34*34*16`` input + ``144x128`` accs);
the MobileNetV2 1x1 head (``C=320``) peaks at ~0.5 MB of accumulator.
The dx kernel carries one ``(Hp*Wp, C)`` f32 accumulator (74 KB at the
stage-0 worst case) next to its ``(Ho, Wo, BN)`` gy block.
Non-128-multiple ``dout`` is padded to the clamped ``BN`` tile and cropped
on return; padded columns accumulate zeros and (like ``psg_matmul``'s
padding caveat) count as fallback work in the stats — the ratio reports
*executed* tiles, which is what hardware pays for.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.psg_matmul import CODE_PRECISION

DEFAULT_BN = 128


def conv_out_hw(hp: int, wp: int, k: int, stride: int) -> Tuple[int, int]:
    """VALID output extent of a pre-padded ``(Hp, Wp)`` input."""
    return (hp - k) // stride + 1, (wp - k) // stride + 1


def to_tap_major(w: jnp.ndarray, k: int, cin: int) -> jnp.ndarray:
    """(k*k*cin, dout) patch-major (channel-major rows) -> tap-major."""
    dout = w.shape[-1]
    return w.reshape(cin, k, k, dout).transpose(1, 2, 0, 3) \
            .reshape(k * k * cin, dout)


def to_patch_major(wt: jnp.ndarray, k: int, cin: int) -> jnp.ndarray:
    """Inverse of :func:`to_tap_major` (exact for sign tensors)."""
    dout = wt.shape[-1]
    return wt.reshape(k, k, cin, dout).transpose(2, 0, 1, 3) \
             .reshape(k * k * cin, dout)


def _tap_window(x: jnp.ndarray, t: int, k: int, stride: int,
                ho: int, wo: int) -> jnp.ndarray:
    """Strided gather of filter tap ``t`` from an ``(Hp, Wp, C)`` block:
    the (ho*wo, C) column slice of the implicit im2col matrix."""
    ki, kj = t // k, t % k
    c = x.shape[-1]
    win = lax.slice(x, (ki, kj, 0),
                    (ki + (ho - 1) * stride + 1,
                     kj + (wo - 1) * stride + 1, c),
                    (stride, stride, 1))
    return win.reshape(ho * wo, c)


def _conv_fwd_kernel(x_ref, w_ref, o_ref, *, k: int, stride: int,
                     ho: int, wo: int):
    """One (image, dout-tile): unrolled implicit-GEMM tap loop."""
    x = x_ref[0].astype(jnp.float32)
    c = x.shape[-1]
    acc = jnp.zeros((ho * wo, o_ref.shape[-1]), jnp.float32)
    for t in range(k * k):
        acc = acc + jnp.dot(_tap_window(x, t, k, stride, ho, wo),
                            w_ref[t * c:(t + 1) * c, :].astype(jnp.float32),
                            preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(ho, wo, -1).astype(o_ref.dtype)


def _conv_grad_x_kernel(g_ref, w_ref, o_ref, acc, *, k: int, stride: int,
                        hp: int, wp: int, ho: int, wo: int, n_j: int):
    """One (image, dout-tile) step of the implicit transposed conv.

    Transpose of the forward tap loop: ``dx[p, q] = sum_t gy[(p-ki)/s,
    (q-kj)/s] @ w_t^T`` over taps where the division is exact.  dx is
    decomposed into ``s x s`` spatial phases ``(pi, pj)``; within a phase
    only taps with ``ki = pi (mod s)`` contribute and the gather becomes a
    *stride-1* shifted window of the zero-padded gy block — dilated-window
    indexing instead of the col2im scatter.  The dout axis is the
    reduction axis: partials accumulate in the f32 ``acc`` tile and the dx
    block is written exactly once, on the last dout tile.
    """
    s = stride
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    g = g_ref[0].astype(jnp.float32)                    # (ho, wo, bn)
    bn = g.shape[-1]
    c = acc.shape[-1]
    nu, nv = -(-hp // s), -(-wp // s)                   # phase lattice extent
    a_max = (k - 1) // s                                # max tap phase offset
    # pad so every shifted (nu, nv) window gather is in range: rows u - a
    # for u in [0, nu), a in [0, a_max] span [-a_max, nu - 1]
    gp = jnp.pad(g, ((a_max, nu - ho), (a_max, nv - wo), (0, 0)))
    phase_rows = []
    for pi in range(s):
        prow = []
        for pj in range(s):
            part = jnp.zeros((nu * nv, c), jnp.float32)
            for a in range(-(-(k - pi) // s)):          # ki = pi + s*a < k
                for b in range(-(-(k - pj) // s)):
                    t = (pi + s * a) * k + (pj + s * b)
                    win = lax.slice(gp, (a_max - a, a_max - b, 0),
                                    (a_max - a + nu, a_max - b + nv, bn))
                    part = part + jnp.dot(
                        win.reshape(nu * nv, bn),
                        w_ref[t * c:(t + 1) * c, :].astype(jnp.float32).T,
                        preferred_element_type=jnp.float32)
            prow.append(part.reshape(nu, nv, c))
        phase_rows.append(jnp.stack(prow, axis=2))      # (nu, nv, s, c)
    full = jnp.stack(phase_rows, axis=1)                # (nu, s, nv, s, c)
    full = full.reshape(nu * s, nv * s, c)[:hp, :wp, :]
    acc[...] += full.reshape(hp * wp, c)

    @pl.when(j == n_j - 1)
    def _finish():
        o_ref[0] = acc[...].reshape(hp, wp, c).astype(o_ref.dtype)


def _conv_pred_kernel(xm_ref, gm_ref, out_ref, acc, *, k: int, stride: int,
                      ho: int, wo: int, n_b: int):
    """Predictor-only implicit weight-grad (pass 1: the tau source)."""
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xm = xm_ref[0].astype(jnp.float32)
    gm = gm_ref[0].astype(jnp.float32).reshape(ho * wo, -1)
    c = xm.shape[-1]
    for t in range(k * k):
        acc[t * c:(t + 1) * c, :] += jnp.dot(
            _tap_window(xm, t, k, stride, ho, wo).T, gm,
            preferred_element_type=jnp.float32, precision=CODE_PRECISION)

    @pl.when(b == n_b - 1)
    def _finish():
        out_ref[...] = acc[...]


def _conv_grad_w_kernel(xm_ref, gm_ref, xq_ref, gq_ref, tau_ref,
                        out_ref, stats_ref, acc_msb, acc_full,
                        *, k: int, stride: int, ho: int, wo: int, n_j: int,
                        n_b: int):
    """Fused PSG weight grad: both accumulators carried across images,
    tau-gated per (tap, dout-tile) on the last reduction step.  ``tau`` is
    a (1,) SMEM scalar; the per-tile fallback flags go to a flat SMEM
    vector, entry ``t * n_j + j`` for tap ``t`` of dout tile ``j``."""
    j, b = pl.program_id(0), pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        acc_msb[...] = jnp.zeros_like(acc_msb)
        acc_full[...] = jnp.zeros_like(acc_full)

    xm = xm_ref[0].astype(jnp.float32)
    xq = xq_ref[0].astype(jnp.float32)
    gm = gm_ref[0].astype(jnp.float32).reshape(ho * wo, -1)
    gq = gq_ref[0].astype(jnp.float32).reshape(ho * wo, -1)
    c = xm.shape[-1]
    for t in range(k * k):
        acc_msb[t * c:(t + 1) * c, :] += jnp.dot(
            _tap_window(xm, t, k, stride, ho, wo).T, gm,
            preferred_element_type=jnp.float32, precision=CODE_PRECISION)
        acc_full[t * c:(t + 1) * c, :] += jnp.dot(
            _tap_window(xq, t, k, stride, ho, wo).T, gq,
            preferred_element_type=jnp.float32, precision=CODE_PRECISION)

    @pl.when(b == n_b - 1)
    def _finish():
        tau = tau_ref[0]
        for t in range(k * k):
            g_msb = acc_msb[t * c:(t + 1) * c, :]
            g_full = acc_full[t * c:(t + 1) * c, :]
            conf = jnp.abs(g_msb) >= tau
            out_ref[t * c:(t + 1) * c, :] = jnp.where(
                conf, jnp.sign(g_msb), jnp.sign(g_full)).astype(jnp.int8)
            stats_ref[t * n_j + j] = jnp.logical_not(
                jnp.all(conf)).astype(jnp.int32)


def _pad_dout(a: jnp.ndarray, bn: int) -> jnp.ndarray:
    p = (-a.shape[-1]) % bn
    if p:
        pad = [(0, 0)] * (a.ndim - 1) + [(0, p)]
        a = jnp.pad(a, pad)
    return a


def conv_fwd_pallas(xp: jnp.ndarray, w: jnp.ndarray, *, k: int, stride: int,
                    bn: int = DEFAULT_BN, interpret: bool = True
                    ) -> jnp.ndarray:
    """Implicit-GEMM conv forward.

    ``xp``: pre-padded NHWC input ``(B, Hp, Wp, C)``; ``w``: patch-major
    ``(k*k*C, dout)``.  Returns ``(B, Ho, Wo, dout)`` in ``xp.dtype``.
    """
    B, Hp, Wp, C = xp.shape
    dout = w.shape[-1]
    ho, wo = conv_out_hw(Hp, Wp, k, stride)
    bn_ = min(bn, dout)
    wt = _pad_dout(to_tap_major(w, k, C), bn_)
    doutp = wt.shape[-1]
    n_j = doutp // bn_
    y = pl.pallas_call(
        functools.partial(_conv_fwd_kernel, k=k, stride=stride, ho=ho, wo=wo),
        grid=(B, n_j),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((k * k * C, bn_), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, bn_), lambda b, j: (b, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, ho, wo, doutp), xp.dtype),
        interpret=interpret,
    )(xp, wt)
    return y[..., :dout]


def conv_grad_x_pallas(gq: jnp.ndarray, wq: jnp.ndarray, *, k: int,
                       stride: int, hp: int, wp: int, bn: int = DEFAULT_BN,
                       interpret: bool = True) -> jnp.ndarray:
    """Implicit transposed-conv input gradient.

    ``gq``: quantized output-gradient ``(B, Ho, Wo, dout)``; ``wq``:
    patch-major ``(k*k*C, dout)`` quantized weight; ``hp``/``wp``: the
    pre-padded input extent the forward consumed.  Returns ``dx (B, hp,
    wp, C)`` accumulated in float32 — value-equal to the col2im reference
    (``ref.conv_grad_x_ref``) up to fp32 tap-summation order, with no
    dpatches tensor and no k^2 HBM read-modify-write scatter passes: gy is
    read once, dx is written once.
    """
    B, ho, wo, dout = gq.shape
    C = wq.shape[0] // (k * k)
    bn_ = min(bn, dout)
    wt = _pad_dout(to_tap_major(wq, k, C), bn_)
    gp = _pad_dout(gq, bn_)
    n_j = gp.shape[-1] // bn_
    return pl.pallas_call(
        functools.partial(_conv_grad_x_kernel, k=k, stride=stride,
                          hp=hp, wp=wp, ho=ho, wo=wo, n_j=n_j),
        grid=(B, n_j),
        in_specs=[
            pl.BlockSpec((1, ho, wo, bn_), lambda b, j: (b, 0, 0, j)),
            pl.BlockSpec((k * k * C, bn_), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, hp, wp, C), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hp, wp, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hp * wp, C), jnp.float32)],
        interpret=interpret,
    )(gp, wt)


def conv_grad_w_predictor_pallas(xm: jnp.ndarray, gm: jnp.ndarray,
                                 *, k: int, stride: int,
                                 bn: int = DEFAULT_BN,
                                 interpret: bool = True) -> jnp.ndarray:
    """Predictor product ``gather(x_msb)^T @ g_msb`` (fp32, patch-major) —
    pass 1 of the two-pass PSG conv grad; its global max sets ``tau``."""
    B, Hp, Wp, C = xm.shape
    dout = gm.shape[-1]
    ho, wo = conv_out_hw(Hp, Wp, k, stride)
    bn_ = min(bn, dout)
    gmp = _pad_dout(gm, bn_)
    doutp = gmp.shape[-1]
    n_j = doutp // bn_
    out = pl.pallas_call(
        functools.partial(_conv_pred_kernel, k=k, stride=stride, ho=ho,
                          wo=wo, n_b=B),
        grid=(n_j, B),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda j, b: (b, 0, 0, 0)),
            pl.BlockSpec((1, ho, wo, bn_), lambda j, b: (b, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((k * k * C, bn_), lambda j, b: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k * k * C, doutp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k * k * C, bn_), jnp.float32)],
        interpret=interpret,
    )(xm, gmp)
    return to_patch_major(out[:, :dout], k, C)


def conv_grad_w_pallas(xm: jnp.ndarray, gm: jnp.ndarray,
                       xq: jnp.ndarray, gq: jnp.ndarray, tau: jnp.ndarray,
                       *, k: int, stride: int, bn: int = DEFAULT_BN,
                       interpret: bool = True
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tile-level PSG conv weight gradient (implicit im2col gather).

    Args: code tensors — ``xm``/``xq``: padded-input MSB / full codes
    ``(B, Hp, Wp, C)``; ``gm``/``gq``: output-grad codes ``(B, Ho, Wo,
    dout)``; ``tau`` scalar threshold in predictor code units.
    Returns ``(sign (k*k*C, dout) int8 patch-major, tile_fallback
    (k*k, ceil(dout/BN)) int32)``.
    """
    B, Hp, Wp, C = xm.shape
    dout = gm.shape[-1]
    ho, wo = conv_out_hw(Hp, Wp, k, stride)
    bn_ = min(bn, dout)
    gmp, gqp = _pad_dout(gm, bn_), _pad_dout(gq, bn_)
    doutp = gmp.shape[-1]
    n_j = doutp // bn_
    out, stats = pl.pallas_call(
        functools.partial(_conv_grad_w_kernel, k=k, stride=stride, ho=ho,
                          wo=wo, n_j=n_j, n_b=B),
        grid=(n_j, B),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda j, b: (b, 0, 0, 0)),
            pl.BlockSpec((1, ho, wo, bn_), lambda j, b: (b, 0, 0, j)),
            pl.BlockSpec((1, Hp, Wp, C), lambda j, b: (b, 0, 0, 0)),
            pl.BlockSpec((1, ho, wo, bn_), lambda j, b: (b, 0, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),          # tau scalar
        ],
        out_specs=[
            pl.BlockSpec((k * k * C, bn_), lambda j, b: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),          # tile flags
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k * k * C, doutp), jnp.int8),
            jax.ShapeDtypeStruct((k * k * n_j,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((k * k * C, bn_), jnp.float32),
            pltpu.VMEM((k * k * C, bn_), jnp.float32),
        ],
        interpret=interpret,
    )(xm, gmp, xq, gqp, tau.reshape(1).astype(jnp.float32))
    sign = to_patch_major(out[:, :dout], k, C)
    return sign, stats.reshape(k * k, n_j)
