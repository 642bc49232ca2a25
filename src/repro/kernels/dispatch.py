"""Kernel backend dispatch — the ONE place that picks how a kernel runs.

Every PSG/quantization op can execute on one of three backends
(DESIGN.md §Dispatch):

* ``"reference"`` — the element-level pure-jnp oracle (``kernels/ref.py``).
  Test-only semantics anchor; also the safety hatch for platforms where the
  Pallas interpreter misbehaves.
* ``"interpret"`` — the tile-level Pallas kernel executed by the Pallas
  interpreter (CPU containers, debugging).  Same tile semantics and the same
  fallback-tile statistics as the compiled path.
* ``"mosaic"`` — the tile-level kernel lowered through Mosaic on a real TPU.

Selection order, strongest first:

1. an active :func:`override_backend` context (tests, benchmarks);
2. ``PSGConfig.backend`` when it is not ``"auto"`` (per-experiment pin);
3. the process default: ``REPRO_KERNEL_BACKEND`` if set — read ONCE at
   import, never at trace time — else a platform probe
   (``jax.default_backend() == "tpu"`` -> mosaic, else interpret).

This retires the scattered environment reads the seed repo had
(``REPRO_PALLAS_COMPILE`` at ``kernels/ops.py`` import, and
``REPRO_PSG_INT8_GATHER`` *inside the traced forward* of
``core/psg.psg_matmul`` — an env read baked into whichever jit cache entry
traced first).  No environment variable is consulted inside jitted code.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import PSGConfig
from repro.kernels import ops, ref

BACKEND_REFERENCE = "reference"
BACKEND_INTERPRET = "interpret"
BACKEND_MOSAIC = "mosaic"
BACKENDS = (BACKEND_REFERENCE, BACKEND_INTERPRET, BACKEND_MOSAIC)

# retired trace-time env vars; kept as names only so DESIGN.md and the
# migration error message below can point at them.
RETIRED_ENV_VARS = ("REPRO_PALLAS_COMPILE", "REPRO_PSG_INT8_GATHER")

_ENV_DEFAULT = os.environ.get("REPRO_KERNEL_BACKEND", "").strip().lower()

_state = threading.local()
_process_default: Optional[str] = None


def _validate(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r}; expected one of {BACKENDS} "
            f"(note: {', '.join(RETIRED_ENV_VARS)} are retired — use "
            f"PSGConfig.backend or repro.kernels.dispatch)")
    return name


def platform_default() -> str:
    """Probe the platform: compiled kernels on TPU, interpreter elsewhere."""
    return BACKEND_MOSAIC if jax.default_backend() == "tpu" else BACKEND_INTERPRET


def default_backend() -> str:
    """Process-wide default (env pin at import time, else platform probe)."""
    global _process_default
    if _process_default is None:
        _process_default = _validate(_ENV_DEFAULT) if _ENV_DEFAULT \
            else platform_default()
    return _process_default


def set_default_backend(name: Optional[str]) -> None:
    """Pin (or with ``None`` re-probe) the process-wide default."""
    global _process_default
    _process_default = _validate(name) if name is not None else None


@contextlib.contextmanager
def override_backend(name: str):
    """Force a backend for ops *traced* under this context (tests/benches).

    Trace-time only: like every non-argument selection path, it cannot be
    part of a jit cache key.  A function traced inside the context keeps the
    overridden backend for the lifetime of its cache entry, and a function
    already traced outside ignores the override entirely.  Use it around
    fresh traces (``jax.jit(f).lower(...)``, first call of a new function);
    to pin the backend of long-lived jitted train steps, set
    ``PSGConfig.backend`` — the config is a static jit argument, so the
    cache does the right thing.
    """
    _validate(name)
    prev = getattr(_state, "override", None)
    _state.override = name
    try:
        yield
    finally:
        _state.override = prev


def resolve_backend(cfg: Optional[PSGConfig] = None) -> str:
    """The backend an op traced right now should use."""
    override = getattr(_state, "override", None)
    if override is not None:
        return override
    if cfg is not None and cfg.backend != "auto":
        return _validate(cfg.backend)
    return default_backend()


# ---------------------------------------------------------------------------
# dispatched ops — call these, not kernels.ops / kernels.ref directly
# ---------------------------------------------------------------------------


def psg_grad_w(x2: jnp.ndarray, gy2: jnp.ndarray, cfg: PSGConfig
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PSG weight-gradient sign + measured fallback ratio.

    Tile-level Pallas kernel on the interpret/mosaic backends (fallback
    ratio = fraction of output tiles that ran the full product); element
    level on the reference backend (fallback ratio = fraction of entries
    below the confidence threshold).  Both are in [0, 1] and feed the same
    energy model (``core/energy.py``).

    Traced under a data-parallel mesh (``distributed.sharding
    .activation_sharding``), the kernel runs per shard and the partial
    products are summed (``ops.psg_grad_w`` with mesh axes): the compiler
    cannot partition a Mosaic kernel by itself.
    """
    from repro.distributed.sharding import ctx_data_axes

    backend = resolve_backend(cfg)
    xf = x2.astype(jnp.float32)
    gf = gy2.astype(jnp.float32)
    if backend == BACKEND_REFERENCE:
        return (ref.psg_grad_w_ref(xf, gf, cfg),
                ref.psg_fallback_ratio_ref(xf, gf, cfg))
    mesh, axes = ctx_data_axes(x2.shape[0])
    return ops.psg_grad_w(xf, gf, cfg, interpret=backend != BACKEND_MOSAIC,
                          mesh=mesh if axes else None, axes=axes)


def psg_depthwise_grad_w(x: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                         *, stride: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PSG weight gradient of a 3x3 depthwise conv: ``(sign (9, C),
    fallback)``, the fallback the share of ``(tap, channel)`` entries
    whose predictor fell below the threshold, on every backend.

    The depthwise kernel (``kernels/psg_depthwise.py``) on the
    interpret/mosaic backends; the element-level oracle on the reference
    backend.  ``x`` is the conv's NHWC input, ``gy`` its output gradient.
    """
    backend = resolve_backend(cfg)
    xf = x.astype(jnp.float32)
    gf = gy.astype(jnp.float32)
    if backend == BACKEND_REFERENCE:
        return ref.psg_depthwise_grad_w_ref(xf, gf, cfg, stride)
    return ops.psg_depthwise_grad_w(xf, gf, cfg, stride,
                                    interpret=backend != BACKEND_MOSAIC)


def quantize(x: jnp.ndarray, bits: int,
             cfg: Optional[PSGConfig] = None) -> jnp.ndarray:
    """Fake-quantize through the backend the context resolves to."""
    backend = resolve_backend(cfg)
    if backend == BACKEND_REFERENCE:
        return ref.quantize_ref(x, bits)
    return ops.quantize(x, bits, interpret=backend != BACKEND_MOSAIC)


def conv_fwd(xq: jnp.ndarray, wq: jnp.ndarray, cfg: Optional[PSGConfig],
             *, k: int, stride: int) -> jnp.ndarray:
    """Conv forward on pre-quantized operands (pre-padded NHWC input,
    patch-major weight).

    Implicit-GEMM Pallas kernel (``kernels/conv.py``) on the
    interpret/mosaic backends — the im2col operand is gathered inside the
    kernel, never materialized in HBM; materialized im2col + single GEMM
    on the reference backend (the semantics anchor, value-equal up to fp32
    tap-summation order).
    """
    backend = resolve_backend(cfg)
    if backend == BACKEND_REFERENCE:
        return ref.conv_fwd_ref(xq, wq, k, stride)
    return ops.conv_fwd(xq, wq, k, stride,
                        interpret=backend != BACKEND_MOSAIC)


def conv_grad_x(gq: jnp.ndarray, wq: jnp.ndarray,
                cfg: Optional[PSGConfig], *, k: int, stride: int,
                hp: int, wp: int) -> jnp.ndarray:
    """Conv input gradient on pre-quantized operands (``dx``).

    Implicit transposed-conv Pallas kernel (``kernels/conv.py``) on the
    interpret/mosaic backends — gy windows and tap-major weight slices are
    gathered inside the kernel, dx accumulates in an f32 VMEM tile and is
    written once; per-tap col2im scatter-add loop (f32 accumulation) on
    the reference backend, the demoted semantics anchor.  Value-equal up
    to fp32 tap-summation order.
    """
    backend = resolve_backend(cfg)
    gf = gq.astype(jnp.float32)
    wf = wq.astype(jnp.float32)
    if backend == BACKEND_REFERENCE:
        return ref.conv_grad_x_ref(gf, wf, k, stride, hp, wp)
    return ops.conv_grad_x(gf, wf, k, stride, hp, wp,
                           interpret=backend != BACKEND_MOSAIC)


def conv_grad_w(xp: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                *, k: int, stride: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PSG conv weight-gradient sign + measured fallback ratio.

    Same contract as :func:`psg_grad_w` with the im2col operand implicit:
    tile-level kernel on interpret/mosaic (fallback ratio = fraction of
    ``(C, BN)``-per-tap output tiles that ran the full product); element
    level on the reference backend.  Both feed the same probe channel
    (``core/psg.py``) and the same energy model.
    """
    backend = resolve_backend(cfg)
    xf = xp.astype(jnp.float32)
    gf = gy.astype(jnp.float32)
    if backend == BACKEND_REFERENCE:
        return (ref.conv_grad_w_ref(xf, gf, cfg, k, stride),
                ref.conv_fallback_ratio_ref(xf, gf, cfg, k, stride))
    return ops.conv_grad_w(xf, gf, cfg, k, stride,
                           interpret=backend != BACKEND_MOSAIC)


def attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  cfg: Optional[PSGConfig], *, causal: bool = True
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused attention forward: ``(o, lse)`` with lse (B, nh, S) fp32.

    Flash Pallas kernel on the interpret/mosaic backends (O(S·d) HBM
    traffic, lse emitted from the same pass); materialized softmax oracle
    + direct logsumexp on the reference backend.  Either way the lse is
    the only residual the backward needs beyond the operands.
    """
    backend = resolve_backend(cfg)
    if backend == BACKEND_REFERENCE:
        o = ref.flash_attention_oracle(q, k, v, causal).astype(q.dtype)
        return o, ref.attention_lse_ref(q, k, causal)
    return ops.flash_attention_fwd(q, k, v, causal=causal,
                                   interpret=backend != BACKEND_MOSAIC)


def attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  o: jnp.ndarray, lse: jnp.ndarray, do: jnp.ndarray,
                  cfg: PSGConfig, *, causal: bool = True):
    """PSG attention backward: ``(dq, dk, dv, fallback_ratio)``.

    Recomputed-tile Pallas kernels on the interpret/mosaic backends
    (fp32 dq; dual MSB/full code-product accumulators for dk/dv with the
    Eq. (2) select applied on the group-summed kv-head products — fallback
    ratio = fraction of (bk x hd) kv-tiles that needed the full product);
    element level on the reference backend (materialized probabilities,
    same select, element-granularity tiles).  Both ratios are in [0, 1]
    and feed the same probe -> energy channel as the matmul/conv PSG ops.
    """
    backend = resolve_backend(cfg)
    if backend == BACKEND_REFERENCE:
        return ref.psg_attention_bwd_ref(q, k, v, do, cfg, causal)
    return ops.flash_attention_bwd(q, k, v, o, lse, do, cfg, causal=causal,
                                   interpret=backend != BACKEND_MOSAIC)


# ---------------------------------------------------------------------------
# shipped-kernel registry — the kernel linter's worklist
# ---------------------------------------------------------------------------


def conv_lint_geometries() -> Dict[str, Tuple[int, int, int, int, int]]:
    """Kernel-facing conv geometries the linter must cover, one per conv
    *kind* that actually ships: ``kind -> (k, stride, hw, cin, cout)``.

    Derived from ``configs/paper_cnns.resnet_conv_shapes`` (deepest-stage
    representative of each kind, ``psg.conv2d``'s ``k < stride``
    pre-subsample normalization applied — the kernels never see
    ``k < stride``), plus the MobileNetV2-style ``point`` 1x1 with a
    non-128-multiple ``dout`` so the padded dout tile is linted too.
    ``cout`` is widened to 256 so the dout axis tiles (grid > 1) — a
    coverage or accumulator bug cannot hide behind a degenerate grid.
    """
    from repro.configs.paper_cnns import resnet_conv_shapes

    by_kind = {}
    for c in resnet_conv_shapes(depth=14, width=16, batch=4):
        by_kind[c.kind] = c                 # last occurrence: deepest stage
    geoms: Dict[str, Tuple[int, int, int, int, int]] = {}
    for kind, c in sorted(by_kind.items()):
        k, s, hw = c.k, c.stride, c.hw
        if k < s:                           # 1x1 downsample: pre-subsampled
            hw, s = -(-hw // s), 1
        geoms[kind] = (k, s, hw, c.cin, 256)
    geoms["point"] = (1, 1, 4, 40, 200)     # padded dout tile (n_j = 2)
    return geoms


def shipped_kernels() -> Dict[str, Tuple[Callable, tuple]]:
    """Every Pallas kernel this repo ships, with representative abstract
    instantiations: ``name -> (fn, args)`` where ``args`` are
    :class:`jax.ShapeDtypeStruct` trees suitable for ``jax.make_jaxpr(fn)``.

    The static kernel linter (``analysis/kernel_lint.py``) traces each entry
    and checks VMEM budgets, MXU tile alignment, BlockSpec index-map
    coverage, and accumulator init/finish discipline.  The conv kernels are
    registered once per :func:`conv_lint_geometries` kind (``name[kind]``)
    — a hardcoded single geometry would let a geometry-dependent violation
    in the 1x1/strided cases that actually ship slip past the linter.
    Shapes are chosen so every grid has more than one step along each axis
    the kernel tiles.
    """
    from repro.kernels import (conv, flash_attn, psg_depthwise, psg_matmul,
                               quant)

    f32 = jnp.float32
    i8 = jnp.int8
    i16 = jnp.int16
    S = jax.ShapeDtypeStruct
    # PSG matmul operands: N=1024 tokens, din=256 -> dout=256 (grid 2x2x2)
    xm, gm = S((1024, 256), i8), S((1024, 256), i8)
    tau = S((), f32)
    # attention operands: S=256 (2 q-blocks, 2 kv-blocks), GQA 4->2 heads.
    # Registered at BOTH fp32 and the model's real bf16 activation dtype —
    # the bf16 rows make precision_lint's narrowed probe exercise the
    # attention kernels with narrow operands instead of skipping them
    # (lse/delta stay fp32, matching the forward's residual contract).
    bf16 = jnp.bfloat16
    q = S((2, 256, 4, 128), f32)
    kv = S((2, 256, 2, 128), f32)
    qb = S((2, 256, 4, 128), bf16)
    kvb = S((2, 256, 2, 128), bf16)
    rows = S((2, 4, 256), f32)              # lse / delta residual rows
    scales6 = S((6,), f32)
    lims = (127.0, 7.0, 32767.0, 511.0)     # default PSGConfig code limits
    entries: Dict[str, Tuple[Callable, tuple]] = {
        "predictor_matmul_pallas": (
            lambda a, b: psg_matmul.predictor_matmul_pallas(
                a, b, interpret=True),
            (xm, gm)),
        # the same kernel on the full 8-bit x 16-bit codes
        "predictor_matmul_pallas[full]": (
            lambda a, b: psg_matmul.predictor_matmul_pallas(
                a, b, interpret=True),
            (S((1024, 256), i8), S((1024, 256), i16))),
        # PSG depthwise: C=192 is not a multiple of 128 (one channel tile
        # as wide as the array), M=2048 rows in 4 row tiles; C=256 in two
        # 128-lane tiles
        "psg_depthwise_products": (
            lambda a, b, c, d: psg_depthwise.psg_depthwise_products(
                a, b, c, d, bm=512, interpret=True),
            (S((9, 2048, 192), i8), S((2048, 192), i16),
             S((9, 2048, 192), i8), S((2048, 192), i16))),
        "psg_depthwise_products[lanes]": (
            lambda a, b, c, d: psg_depthwise.psg_depthwise_products(
                a, b, c, d, bm=512, interpret=True),
            (S((9, 2048, 256), i8), S((2048, 256), i16),
             S((9, 2048, 256), i8), S((2048, 256), i16))),
        "quantize_pallas": (
            functools.partial(quant.quantize_pallas, bits=8, interpret=True),
            (S((512, 1024), f32,),)),
        "flash_attention": (
            functools.partial(flash_attn.flash_attention, causal=True,
                              interpret=True),
            (q, kv, kv)),
        "flash_attention[lse]": (
            functools.partial(flash_attn.flash_attention, causal=True,
                              interpret=True, return_lse=True),
            (q, kv, kv)),
        "flash_attention[bf16]": (
            functools.partial(flash_attn.flash_attention, causal=True,
                              interpret=True, return_lse=True),
            (qb, kvb, kvb)),
        "flash_bwd_dq_pallas": (
            functools.partial(flash_attn.flash_bwd_dq_pallas, causal=True,
                              interpret=True),
            (q, kv, kv, q, rows, rows)),
        "flash_bwd_dq_pallas[bf16]": (
            functools.partial(flash_attn.flash_bwd_dq_pallas, causal=True,
                              interpret=True),
            (qb, kvb, kvb, qb, rows, rows)),
        "flash_bwd_dkv_pallas": (
            functools.partial(flash_attn.flash_bwd_dkv_pallas, lims=lims,
                              causal=True, interpret=True),
            (q, kv, kv, q, rows, rows, scales6)),
        "flash_bwd_dkv_pallas[bf16]": (
            functools.partial(flash_attn.flash_bwd_dkv_pallas, lims=lims,
                              causal=True, interpret=True),
            (qb, kvb, kvb, qb, rows, rows, scales6)),
    }
    B = 4
    for kind, (k, s, hw, cin, cout) in conv_lint_geometries().items():
        pad = k // 2
        hp = hw + 2 * pad
        ho = (hp - k) // s + 1
        cx = S((B, hp, hp, cin), f32)       # pre-padded NHWC input
        cw = S((k * k * cin, cout), f32)    # patch-major weight
        cg = S((B, ho, ho, cout), f32)
        entries[f"conv_fwd_pallas[{kind}]"] = (
            functools.partial(conv.conv_fwd_pallas, k=k, stride=s,
                              interpret=True),
            (cx, cw))
        entries[f"conv_grad_w_predictor_pallas[{kind}]"] = (
            functools.partial(conv.conv_grad_w_predictor_pallas, k=k,
                              stride=s, interpret=True),
            (cx, cg))
        entries[f"conv_grad_w_pallas[{kind}]"] = (
            (lambda a, b, c, d, t, _k=k, _s=s: conv.conv_grad_w_pallas(
                a, b, c, d, t, k=_k, stride=_s, interpret=True)),
            (cx, cg, cx, cg, tau))
        entries[f"conv_grad_x_pallas[{kind}]"] = (
            functools.partial(conv.conv_grad_x_pallas, k=k, stride=s,
                              hp=hp, wp=hp, interpret=True),
            (cg, cw))
    return entries


def kernel_acc_dtypes() -> Dict[str, str]:
    """Declared accumulator-dtype intent per shipped kernel (base name,
    without the ``[geometry]`` suffix of :func:`shipped_kernels` keys).

    This is the contract the precision lint
    (``analysis/precision_lint.py``) holds the kernels to: every
    *float-dtype* ref accumulator the dataflow engine finds in a kernel's
    trace must match the intent declared here, and every shipped kernel
    must declare one.  Integer side-channels (fallback counters, sign
    votes) are exempt — they saturate, they don't lose low-order partial
    sums.  All kernels accumulate in float32: narrow operands are a
    bandwidth story, never an accumulation story (the PR 7 lesson).
    """
    return {
        "predictor_matmul_pallas": "float32",
        "psg_depthwise_products": "float32",
        "quantize_pallas": "float32",
        "flash_attention": "float32",
        "flash_bwd_dq_pallas": "float32",
        "flash_bwd_dkv_pallas": "float32",
        "conv_fwd_pallas": "float32",
        "conv_grad_w_predictor_pallas": "float32",
        "conv_grad_w_pallas": "float32",
        "conv_grad_x_pallas": "float32",
    }
