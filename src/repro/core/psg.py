"""Algorithm-level technique: Predictive Sign Gradient descent (PSG), §3.3.

The paper's insight: SignSGD only needs ``sign(g_w)``, so instead of
computing the full-precision weight gradient ``g_w = x^T g_y`` and then
taking signs, *predict* the sign from an MSB-only low-precision product

    g_w_msb = (x_msb)^T (g_y_msb)          # 4-bit x, 10-bit g_y

and fall back to the (fixed-point) full product only where the predictor's
magnitude is below an adaptive threshold ``tau = beta * max|g_w_msb|``
(Eq. 2).  The failure probability decays exponentially in predictor
precision (Eq. 3).

TPU adaptation (DESIGN.md §Dispatch): the paper's predictor reuses MSBs
inside a bit-serial MAC — a circuit trick with no TPU analogue.  Here the
predictor is an int8xint8 MXU matmul of the quantized operands (int ops run
at >=2x bf16 peak on v5e) and the *fallback* is tile-level inside the Pallas
kernel (``repro.kernels.psg_matmul``) rather than element-level, because the
MXU is dense.  The ``custom_vjp`` backward below routes the weight gradient
through that tile kernel via ``repro.kernels.dispatch`` — the element-level
reference now lives in ``repro.kernels.ref`` and is test-only.

The backward also *measures* how often tiles fell back to the full product
and reports it as the gradient of a probe input (see :func:`enable` /
:func:`probe_fallback_ratio`): cotangents of a shared probe accumulate
across every PSG matmul in the model, so one extra ``grad`` argument yields
the per-step MAC-weighted fallback ratio that drives ``core/energy.py`` —
measured, not assumed, predictor usage.

Mixed precision follows the paper (after [Banner et al. 2018]): activations/
weights at ``bits_x`` (8), output-gradients at ``bits_g`` (16) — gradients
need more headroom; predictors at 4/10 bits.

Distributed bonus (beyond paper): the weight-gradient leaves PSG as a sign
tensor in {-1, 0, +1}; the data-parallel mean of signs followed by the
SignSGD sign() IS majority vote — i.e. PSG composes into 1-bit gradient
all-reduce compression for free (``optim/majority_vote.py``).
"""
from __future__ import annotations

import contextlib
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.config import PSGConfig
from repro.core.quant import msb_of, quantize, quantize_int
from repro.kernels import dispatch
from repro.kernels.psg_depthwise import depthwise_conv
from repro.kernels.ref import (predictor_confidence_ref,  # test-only oracle
                               psg_grad_w_ref)            # (re-exports)

# Slots of the probe vector.  Each PSG matmul / conv / attention backward
# contributes [fallback_ratio * macs, macs] to slots 0-1, so the
# accumulated ratio is MAC-weighted — a tiny all-fallback layer cannot
# swamp a huge mostly-predicted one (the energy model charges MACs, so
# MACs are the right weight).  Each PSG depthwise backward contributes
# [entries below tau, entries] of its (9, C) weight gradient to slots 2-3.
PROBE_SIZE = 4


def zero_probe() -> jnp.ndarray:
    return jnp.zeros((PROBE_SIZE,), jnp.float32)


def _probe_entry(slot: int, fallback, weight) -> jnp.ndarray:
    """A probe cotangent carrying ``[fallback * weight, weight]`` in the
    slot pair starting at ``slot``."""
    pair = jnp.stack([fallback * weight, jnp.float32(weight)])
    return jnp.pad(pair, (slot, PROBE_SIZE - slot - 2))


def probe_fallback_ratio(probe_grad: jnp.ndarray) -> jnp.ndarray:
    """MAC-weighted measured fallback ratio from a probe cotangent."""
    return probe_grad[0] / jnp.maximum(probe_grad[1], 1.0)


def probe_dw_fallback_ratio(probe_grad: jnp.ndarray) -> jnp.ndarray:
    """Share of the depthwise weight-gradient entries, over every PSG
    depthwise conv of the step, whose predictor fell below ``tau``."""
    return probe_grad[2] / jnp.maximum(probe_grad[3], 1.0)


# ---------------------------------------------------------------------------
# element-level reference statistics (kept here: they are *analysis* tools,
# not kernels — tests and notebooks call them through this module)
# ---------------------------------------------------------------------------


def psg_predictor_usage(x2, gy2, cfg: PSGConfig) -> jnp.ndarray:
    """Fraction of weight-grad entries decided by the MSB predictor."""
    _, pred_ok = predictor_confidence_ref(x2, gy2, cfg)
    return jnp.mean(pred_ok.astype(jnp.float32))


def prediction_error_bound(x2, gy2, cfg: PSGConfig) -> jnp.ndarray:
    """Empirical Chebyshev bound of Eq. (3) on a normalized [-1,1] range."""
    xs = x2 / jnp.maximum(jnp.max(jnp.abs(x2)), 1e-12)
    gs = gy2 / jnp.maximum(jnp.max(jnp.abs(gy2)), 1e-12)
    dx = 2.0 ** (-(cfg.bits_x_msb - 1))
    dg = 2.0 ** (-(cfg.bits_g_msb - 1))
    g_full = xs.T @ gs
    tau = cfg.beta * jnp.max(jnp.abs(g_full))
    # E1/E2 with the H_{p,n} denominators lower-bounded by tau (worst case)
    e1 = jnp.sum(jnp.sum(gs ** 2, axis=0)) / (12.0 * tau ** 2)
    e2 = jnp.sum(jnp.sum(xs ** 2, axis=0)) / (12.0 * tau ** 2)
    return dx ** 2 * e1 + dg ** 2 * e2


# ---------------------------------------------------------------------------
# custom_vjp matmul with PSG backward (tile-level kernel via dispatch)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _psg_matmul(x2: jnp.ndarray, w: jnp.ndarray, probe: jnp.ndarray,
                cfg: PSGConfig) -> jnp.ndarray:
    """(N, din) @ (din, dout) with PSG semantics.

    Forward runs on the ``bits_x`` fixed-point grid (the mixed-precision
    training regime of [15] the paper adopts).  With ``cfg.int8_gather`` the
    weight is quantized to *integer codes on its FSDP shard* and explicitly
    replicated before dequantization — placing the FSDP all-gather on int8
    bytes (2x less wire traffic than bf16; the paper's §3.3 low-precision
    data-movement saving applied to the collective term).

    ``probe`` is a zeros((2,)) carrier whose cotangent reports
    [fallback_ratio * macs, macs] from the backward kernel — see module
    docstring.
    """
    xq = quantize(x2, cfg.bits_x)
    if cfg.int8_gather:
        from repro.distributed.sharding import replicate
        codes, s = quantize_int(w, cfg.bits_x)
        codes = replicate(codes)              # int8 on the wire
        wq = codes.astype(xq.dtype) * s.astype(xq.dtype)
    else:
        wq = quantize(w, cfg.bits_x).astype(xq.dtype)
    return xq @ wq


def _psg_fwd(x2, w, probe, cfg):
    return _psg_matmul(x2, w, probe, cfg), (x2, w)


def _psg_bwd(cfg, res, gy):
    # precision: scope — origin tag for analysis/dataflow.py reports: any
    # narrow accumulator found downstream names this backward as its site
    with jax.named_scope("precision:psg_bwd"):
        return _psg_bwd_impl(cfg, res, gy)


def _psg_bwd_impl(cfg, res, gy):
    x2, w = res
    gq = quantize(gy, cfg.bits_g)
    wq = quantize(w, cfg.bits_x)
    dx = (gq @ wq.T.astype(gq.dtype)).astype(x2.dtype)
    # weight gradient: tile-level Eq. (2) through the kernel dispatch layer
    # (Pallas interpret on CPU, Mosaic on TPU, element-level oracle only
    # when explicitly pinned to the reference backend).
    sign, fallback = dispatch.psg_grad_w(x2, gy, cfg)
    dw = sign.astype(w.dtype)
    macs = jnp.float32(x2.shape[0]) * x2.shape[1] * gy.shape[1]
    return dx, dw, _probe_entry(0, fallback, macs)


_psg_matmul.defvjp(_psg_fwd, _psg_bwd)


def psg_matmul(x2: jnp.ndarray, w: jnp.ndarray, cfg: PSGConfig) -> jnp.ndarray:
    """Public PSG matmul; picks up the active stats probe (if any)."""
    return _psg_matmul(x2, w, _current_probe(), cfg)


# ---------------------------------------------------------------------------
# fused implicit-GEMM convolution (PSGConfig.fused_conv)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _psg_conv2d(xp: jnp.ndarray, w: jnp.ndarray, probe: jnp.ndarray,
                k: int, stride: int, cfg: PSGConfig) -> jnp.ndarray:
    """NHWC conv ``(B, Hp, Wp, C) x (k*k*C, dout)`` with PSG semantics,
    without materializing the im2col operand.

    ``xp`` is pre-padded (padding lives OUTSIDE the custom_vjp so autodiff
    crops ``dx`` for free).  Forward quantizes both operands onto the
    ``bits_x`` grid — element-wise on the padded input, which is the same
    grid the im2col path puts on the patch tensor (gathering commutes with
    the per-tensor code map; the ``k < stride`` case where it would not is
    normalized away in :func:`conv2d`) — and runs the implicit-GEMM kernel
    through the dispatch layer.  ``probe`` is the shared fallback-stats
    carrier (module docstring).
    """
    xq = quantize(xp, cfg.bits_x)
    if cfg.int8_gather:
        from repro.distributed.sharding import replicate
        codes, s = quantize_int(w, cfg.bits_x)
        codes = replicate(codes)              # int8 on the wire
        wq = codes.astype(xq.dtype) * s.astype(xq.dtype)
    else:
        wq = quantize(w, cfg.bits_x).astype(xq.dtype)
    return dispatch.conv_fwd(xq, wq, cfg, k=k, stride=stride)


def _psg_conv2d_fwd(xp, w, probe, k, stride, cfg):
    return _psg_conv2d(xp, w, probe, k, stride, cfg), (xp, w)


def _psg_conv2d_bwd(k, stride, cfg, res, gy):
    # precision: scope — see _psg_bwd; the PR 7 bug lived exactly here
    with jax.named_scope("precision:psg_conv2d_bwd"):
        return _psg_conv2d_bwd_impl(k, stride, cfg, res, gy)


def _psg_conv2d_bwd_impl(k, stride, cfg, res, gy):
    xp, w = res
    B, Hp, Wp, C = xp.shape
    dout = w.shape[-1]
    ho, wo = gy.shape[1], gy.shape[2]
    gq = quantize(gy, cfg.bits_g)
    wq = quantize(w, cfg.bits_x)
    # input gradient: implicit transposed-conv kernel via the dispatch
    # layer — gy windows and tap-major weight slices are gathered inside
    # the kernel (dilated-window indexing for stride > 1), dx accumulates
    # in an f32 VMEM tile and each block is written exactly once.  The
    # old per-tap col2im scatter-add loop (k^2 strided HBM
    # read-modify-write passes) is demoted to kernels/ref.py and serves
    # as the reference-backend anchor; both accumulate in float32.
    dxp = dispatch.conv_grad_x(gq, wq, cfg, k=k, stride=stride,
                               hp=Hp, wp=Wp)
    # weight gradient: tile-level Eq. (2) with the patch gather inside the
    # kernel's reduction loop (dispatch: Pallas interpret on CPU, Mosaic on
    # TPU, element-level oracle when pinned to the reference backend).
    sign, fallback = dispatch.conv_grad_w(xp, gy, cfg, k=k, stride=stride)
    dw = sign.astype(w.dtype)
    macs = jnp.float32(B * ho * wo) * (k * k * C) * dout
    return dxp.astype(xp.dtype), dw, _probe_entry(0, fallback, macs)


_psg_conv2d.defvjp(_psg_conv2d_fwd, _psg_conv2d_bwd)


# ---------------------------------------------------------------------------
# 3x3 depthwise convolution (MobileNetV2's inverted residuals)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _psg_depthwise(x: jnp.ndarray, w: jnp.ndarray, probe: jnp.ndarray,
                   stride: int, cfg: PSGConfig) -> jnp.ndarray:
    """3x3 SAME depthwise conv ``(B, H, W, C) x (9, C)`` with PSG semantics.

    Forward: the input and the weight on the ``bits_x`` grid (per-tensor
    codes; the taps are windows of the quantized input, so they share its
    grid), the windows strided before the multiply-sum, in XLA.  The
    backward's weight gradient is Eq. (2) per ``(tap, channel)`` entry
    through the depthwise kernel (``kernels/psg_depthwise.py``, via the
    dispatch layer); ``probe`` carries its fallback share (module
    docstring).
    """
    xq = quantize(x, cfg.bits_x)
    wq = quantize(w, cfg.bits_x).astype(xq.dtype)
    return depthwise_conv(xq, wq, stride)


def _psg_depthwise_fwd(x, w, probe, stride, cfg):
    return _psg_depthwise(x, w, probe, stride, cfg), (x, w)


def _psg_depthwise_bwd(stride, cfg, res, gy):
    with jax.named_scope("precision:psg_depthwise_bwd"):
        x, w = res
        gq = quantize(gy, cfg.bits_g)
        wq = quantize(w, cfg.bits_x).astype(gq.dtype)
        # input gradient: the transposed depthwise conv of the quantized
        # output gradient with the quantized weight, in XLA
        (dx,) = jax.linear_transpose(
            lambda a: depthwise_conv(a, wq, stride),
            jax.ShapeDtypeStruct(x.shape, gq.dtype))(gq)
        sign, fallback = dispatch.psg_depthwise_grad_w(x, gy, cfg,
                                                       stride=stride)
        return (dx.astype(x.dtype), sign.astype(w.dtype),
                _probe_entry(2, fallback, w.size))


_psg_depthwise.defvjp(_psg_depthwise_fwd, _psg_depthwise_bwd)


def depthwise(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1
              ) -> jnp.ndarray:
    """PSG depthwise conv under the active config and stats probe; model
    code calls it only while a PSG context is active."""
    cfg = active_config()
    if cfg is None:
        raise RuntimeError("psg.depthwise needs an active PSG context")
    return _psg_depthwise(x, w, _current_probe(), stride, cfg)


# ---------------------------------------------------------------------------
# fused flash attention with PSG dk/dv backward (PSGConfig.fused_attention)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _psg_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   probe: jnp.ndarray, causal: bool,
                   cfg: PSGConfig) -> jnp.ndarray:
    """Self-attention ``(B, S, nh, hd) x (B, T, nkv, hd)`` with PSG
    backward semantics; no (S, T) tensor in HBM in either direction.

    Forward is the flash kernel (dispatch layer); the backward recomputes
    probability tiles from the logsumexp residual — fp32 dq, and the
    Eq. (2) MSB-predictor/fallback treatment on the dk/dv contractions.
    ``probe`` is the shared fallback-stats carrier (module docstring):
    attention MACs land in the same MAC-weighted ratio as the matmul/conv
    PSG ops.
    """
    o, _ = dispatch.attention_fwd(q, k, v, cfg, causal=causal)
    return o


def _psg_attention_fwd(q, k, v, probe, causal, cfg):
    o, lse = dispatch.attention_fwd(q, k, v, cfg, causal=causal)
    return o, (q, k, v, o, lse)


def _psg_attention_bwd(causal, cfg, res, gy):
    # precision: scope — origin tag for analysis/dataflow.py (see _psg_bwd)
    with jax.named_scope("precision:psg_attention_bwd"):
        return _psg_attention_bwd_impl(causal, cfg, res, gy)


def _psg_attention_bwd_impl(causal, cfg, res, gy):
    q, k, v, o, lse = res
    dq, dk, dv, fallback = dispatch.attention_bwd(q, k, v, o, lse, gy, cfg,
                                                  causal=causal)
    B, S, nh, hd = q.shape
    T = k.shape[1]
    # score pairs actually computed (causal self-attention: the upper
    # triangle is skipped); x2 for the dv and dk contractions
    pairs = S * (S + 1) // 2 if (causal and S == T) else S * T
    macs = jnp.float32(2 * B * nh * hd) * pairs
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _probe_entry(0, fallback, macs))


_psg_attention.defvjp(_psg_attention_fwd, _psg_attention_bwd)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True) -> jnp.ndarray:
    """Public fused-attention entry point; picks up the active PSG config
    and stats probe.  Callers gate on :func:`fused_attention_active`."""
    cfg = active_config()
    return _psg_attention(q, k, v, _current_probe(), causal, cfg)


def fused_attention_active(cfg: Optional[PSGConfig]) -> bool:
    """Resolve a config's ``fused_attention`` selection at trace time.

    Mirrors :func:`fused_conv_active`: explicit ``True``/``False`` wins;
    the default (``None`` = auto) runs the flash kernels on the
    reference/interpret backends and keeps the materialized/chunked
    softmax paths on Mosaic, which stays opt-in pending a real-TPU
    profile (ROADMAP "Finish the Pallas kernel story").
    """
    if cfg is None:
        return False
    if cfg.fused_attention is not None:
        return cfg.fused_attention
    return dispatch.resolve_backend(cfg) != dispatch.BACKEND_MOSAIC


def fused_conv_active(cfg: Optional[PSGConfig]) -> bool:
    """Resolve a config's ``fused_conv`` selection at trace time.

    Explicit ``True``/``False`` wins; the default (``None`` = auto) runs
    the fused implicit-GEMM path on the reference/interpret backends and
    keeps the materialized im2col path on Mosaic, which stays opt-in
    pending a real-TPU profile (ROADMAP "Finish the Pallas kernel story").
    """
    if cfg is None:
        return False
    if cfg.fused_conv is not None:
        return cfg.fused_conv
    return dispatch.resolve_backend(cfg) != dispatch.BACKEND_MOSAIC


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, k: int = 3,
           stride: int = 1) -> jnp.ndarray:
    """Fused-conv entry point: NHWC ``x`` with a patch-major ``(k*k*C,
    dout)`` weight, SAME padding ``k // 2`` (the models' convention).

    With an active PSG config this routes forward AND weight-gradient
    through the implicit-GEMM kernels; with none it falls back to the
    materialized im2col + plain matmul (correctness anchor — model code
    only selects this path when ``cfg.fused_conv`` is set anyway).

    The ``k < stride`` case (1x1 stride-2 projection shortcut) is
    normalized to a pre-subsampled stride-1 conv first: its im2col patch
    tensor IS the subsample, so quantizing after subsampling keeps the
    quantization grid — and therefore the PSG signs — identical to the
    im2col path's.
    """
    cfg = active_config()
    if k < stride:
        x = x[:, ::stride, ::stride, :]
        stride = 1
    pad = k // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0))) if pad else x
    if cfg is None:
        from repro.kernels.ref import conv_fwd_ref
        return conv_fwd_ref(xp, w, k, stride)
    return _psg_conv2d(xp, w, _current_probe(), k, stride, cfg)


# ---------------------------------------------------------------------------
# trace-time dispatch: layers call psg.einsum / psg.matmul
# ---------------------------------------------------------------------------

_state = threading.local()


def active_config() -> Optional[PSGConfig]:
    cfg = getattr(_state, "cfg", None)
    return cfg if (cfg is not None and cfg.enabled) else None


def _current_probe() -> jnp.ndarray:
    probe = getattr(_state, "probe", None)
    return probe if probe is not None else zero_probe()


@contextlib.contextmanager
def enable(cfg: Optional[PSGConfig], probe: Optional[jnp.ndarray] = None):
    """Route model matmuls through PSG while tracing under this context.

    ``probe``: an optional ``zero_probe()`` array threaded into every PSG
    op; differentiate the enclosing loss w.r.t. it to read the accumulated
    [sum of fallback_ratio * macs, sum of macs, depthwise entries below
    tau, depthwise entries] — the measured per-step ``psg_fallback_ratio``
    and ``psg_dw_fallback_ratio`` (see training/train_step.py).
    """
    prev = getattr(_state, "cfg", None)
    prev_probe = getattr(_state, "probe", None)
    _state.cfg = cfg
    _state.probe = probe
    try:
        yield
    finally:
        _state.cfg = prev
        _state.probe = prev_probe


def matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x: (..., din) @ w: (din, dout), PSG-routed when enabled."""
    cfg = active_config()
    if cfg is None:
        return x @ w.astype(x.dtype)
    lead = x.shape[:-1]
    y2 = psg_matmul(x.reshape(-1, x.shape[-1]), w, cfg)
    return y2.reshape(*lead, w.shape[-1])


def einsum(pattern: str, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """PSG-aware einsum for the weight-matmul patterns used by the models."""
    cfg = active_config()
    if cfg is None:
        return jnp.einsum(pattern, x, w.astype(x.dtype))
    if pattern in ("bsd,dnh->bsnh", "btd,dnh->btnh"):
        B, S, d = x.shape
        _, n, h = w.shape
        y = psg_matmul(x.reshape(B * S, d), w.reshape(d, n * h), cfg)
        return y.reshape(B, S, n, h)
    if pattern == "bsnh,nhd->bsd":
        B, S, n, h = x.shape
        d = w.shape[-1]
        y = psg_matmul(x.reshape(B * S, n * h), w.reshape(n * h, d), cfg)
        return y.reshape(B, S, d)
    if pattern == "bd,dnh->bnh":
        B, d = x.shape
        _, n, h = w.shape
        return psg_matmul(x, w.reshape(d, n * h), cfg).reshape(B, n, h)
    if pattern in ("ecd,edf->ecf", "ecf,efd->ecd"):
        return jax.vmap(lambda xe, we: psg_matmul(xe, we, cfg))(x, w.astype(x.dtype))
    if pattern in ("gecd,edf->gecf", "gecf,efd->gecd"):
        G, E, C, din = x.shape
        dout = w.shape[-1]
        xe = jnp.moveaxis(x, 1, 0).reshape(E, G * C, din)
        ye = jax.vmap(lambda xi, wi: psg_matmul(xi, wi, cfg))(
            xe, w.astype(x.dtype))
        return jnp.moveaxis(ye.reshape(E, G, C, dout), 0, 1)
    # unknown pattern: fall back (no PSG) — keeps correctness, logged by tests
    return jnp.einsum(pattern, x, w.astype(x.dtype))
