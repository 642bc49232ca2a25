"""Pallas TPU kernels: causal flash attention, forward AND backward.

The §Perf analysis (EXPERIMENTS.md) shows training/prefill attention is
memory-bound in the unfused form: the (S, T) score/probability tensors are
materialized in HBM — in BOTH directions (the bf16 probability residual on
the forward, its cotangent plus the dS tensor on the backward).  These
kernels keep every (S, T)-shaped quantity in VMEM tiles:

* **forward** (``flash_attention``): one (q-block x head) output tile with
  running row-max / row-sum accumulators, streaming KV blocks through
  VMEM — O(S·d) HBM traffic instead of O(S·T).  With ``return_lse=True``
  it also emits the per-row logsumexp, the only residual the backward
  needs beyond the operands themselves.
* **backward dq** (``flash_bwd_dq_pallas``): recomputes each probability
  tile from (q, k, lse), forms ``dS = P * (dP - delta) * scale`` in-tile
  and accumulates ``dq += dS @ k`` in VMEM scratch — same grid shape as
  the forward, KV innermost.
* **backward dk/dv** (``flash_bwd_dkv_pallas``): the PSG kernel.  Grid is
  transposed (Q innermost); each kv-block tile carries FOUR fp32 VMEM
  accumulators — the MSB *predictor* products and the full-precision-grid
  code products of the ``dv = P^T dO`` and ``dk = dS^T q`` contractions —
  the same two products ``ops.psg_grad_w`` takes from ``psg_matmul``.
  Operands are quantized **in-tile** onto per-tensor grids whose scalar
  scales come in as kernel operands (probabilities live on the fixed
  [0, 1] grid, so their codes need no data-dependent scale), which keeps
  the code products integer-exact and therefore reproducible by the tiled
  oracle in ``kernels/ref.py`` — the bit-identical sign contract.

GQA note — predictor placement: dk/dv belong to *kv* heads, summed over
the ``g = nh / nkv`` query heads of each group.  A Pallas grid step may
not revisit another step's output block, so the kernel emits per-query-
head partial code products and the Eq. (2) select (predictor-confident →
MSB product, else full product) plus the adaptive threshold
``tau = beta * max|g_msb|`` and the per-tile fallback stats are applied
OUTSIDE the kernel, on the group-summed (T, hd)-shaped products — O(T·d)
work on tensors that never had an (S, T) extent.  This mirrors the
two-pass ``ops.psg_grad_w`` recipe (tau in code units; sign is
scale-invariant) with the finish stage hoisted one level up; see
``psg_attention_select`` and DESIGN.md §Kernels.

Layout: grid = (batch*heads, S/BQ, T/BK) (transposed for dkv), KV/Q
innermost respectively; fp32 accumulators in VMEM scratch; fully masked
causal blocks are skipped with ``pl.when``.  Default tiles (128, 128):
the dkv working set ~= 4 input tiles + 4 output tiles + 4 scratch
accumulators at (128, 128) fp32 ≈ 0.8 MB — deep double-buffering headroom
in 16 MB VMEM.  Validated against ``ref.flash_attention_oracle`` (+ its
vjp) and the tiled PSG product oracle across shape sweeps in interpret
mode (tests/test_flash_bwd.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# shared tile math — the kernels AND the ref.py oracle call these with
# identically-shaped operands, so the fp32 results (and therefore the
# integer code products) agree bit-for-bit between the two paths
# ---------------------------------------------------------------------------


def _dot_nt(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(m, d) x (n, d) -> (m, n), contracting the trailing axes, fp32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(m, n) x (m, d) -> (n, d), contracting the leading axes, fp32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nn(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(m, n) x (n, d) -> (m, d), plain row-major contraction, fp32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def p_tile(q: jnp.ndarray, k: jnp.ndarray, lse: jnp.ndarray,
           valid: jnp.ndarray, scale: float) -> jnp.ndarray:
    """One recomputed probability tile: ``exp(q k^T * scale - lse)`` with
    invalid (future/padded) entries exactly zero.  ``lse``: (bq, 1)."""
    s = _dot_nt(q, k) * scale
    return jnp.where(valid, jnp.exp(s - lse), 0.0)


def ds_tile(p: jnp.ndarray, dp: jnp.ndarray, delta: jnp.ndarray,
            scale: float) -> jnp.ndarray:
    """One dS tile: ``P * (dP - delta) * scale``.  ``delta``: (bq, 1)."""
    return p * (dp - delta) * scale


def codes_tile(x: jnp.ndarray, s, lim: float) -> jnp.ndarray:
    """Integer codes of ``x`` on the grid with scale ``s`` (fp32 values)."""
    return jnp.clip(jnp.round(x / s), -lim, lim)


def qlim(bits: int) -> float:
    return 2.0 ** (bits - 1) - 1.0


def attention_psg_scales(q: jnp.ndarray, v: jnp.ndarray, do: jnp.ndarray,
                         delta: jnp.ndarray, *, bits_x: int, bits_x_msb: int,
                         bits_g: int, bits_g_msb: int) -> jnp.ndarray:
    """The six data-dependent quantization-grid scales the dkv kernel needs,
    packed ``[s_q, s_q_msb, s_do, s_do_msb, s_ds, s_ds_msb]`` (fp32).

    q and dO use the standard per-tensor ``qscale`` grids.  dS is never
    materialized, so its grid comes from the analytic bound
    ``|dS| <= P * (|dP| + |delta|) * scale <= (max_s ||dO_s|| * max_t ||v_t||
    + max|delta|) / sqrt(hd)`` — conservative (typical |dS| is far below
    the bound, so dS predictor codes are small and dk tiles fall back more
    often than a measured-max grid would allow; the fallback ratio honestly
    *measures* that).  The oracle shares these exact scales.
    """
    from repro.core.quant import qscale
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    do32 = do.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    rn_do = jnp.sqrt(jnp.max(jnp.sum(do32 * do32, axis=-1)))
    rn_v = jnp.sqrt(jnp.max(jnp.sum(v32 * v32, axis=-1)))
    bound = jnp.maximum(scale * (rn_do * rn_v + jnp.max(jnp.abs(delta))),
                        1e-12)
    return jnp.stack([
        qscale(q, bits_x), qscale(q, bits_x_msb),
        qscale(do, bits_g), qscale(do, bits_g_msb),
        bound / qlim(bits_g), bound / qlim(bits_g_msb),
    ]).astype(jnp.float32)


def psg_attention_select(msb: jnp.ndarray, full: jnp.ndarray,
                         deq_msb, deq_full, beta: float,
                         tile_t: int = DEFAULT_BK):
    """Eq. (2) finish stage on a group-summed code-product pair.

    Element-level select: predictor-confident entries (``|g_msb| >= tau``,
    ``tau = beta * max|g_msb|`` in code units) take the dequantized MSB
    product, the rest the dequantized full product — identical to the
    element-level oracle by construction.  Tile-level accounting: the
    fraction of (tile_t x hd) kv-tiles containing any fallback entry is
    what the energy model charges full-precision MACs for (the same tile
    granularity the kernel accumulates at).

    Returns ``(values, tile_fallback_ratio)``.
    """
    tau = beta * jnp.max(jnp.abs(msb))
    conf = jnp.abs(msb) >= tau
    vals = jnp.where(conf, msb * deq_msb, full * deq_full)
    B, T, nkv, hd = conf.shape
    pad = (-T) % tile_t
    cpad = jnp.pad(conf, ((0, 0), (0, pad), (0, 0), (0, 0)),
                   constant_values=True) if pad else conf
    tiles = cpad.reshape(B, (T + pad) // tile_t, tile_t, nkv, hd)
    need_full = jnp.any(jnp.logical_not(tiles), axis=(2, 4))
    return vals, jnp.mean(need_full.astype(jnp.float32))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, *refs, n_kv: int, bq: int, bk: int,
                  causal: bool, scale: float, t_real: int, with_lse: bool):
    """One (bh, iq, ik) step: fold KV block ik into the (iq) accumulators."""
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        lse_ref = None
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-masked (future) KV blocks: first kv row > last q row
    run = jnp.logical_or(not causal, ik * bk <= iq * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)              # (BK, hd)
        v = v_ref[0].astype(jnp.float32)
        s = _dot_nt(q, k) * scale
        qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kj < t_real                 # mask padded keys
        if causal:
            valid = jnp.logical_and(valid, kj <= qi)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot_nn(p, v)
        m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = (m_scr[..., 0] +
                             jnp.log(jnp.maximum(l_scr[..., 0], 1e-30)))


def _pad_seq(x: jnp.ndarray, pad: int) -> jnp.ndarray:
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


def _heads_major(x: jnp.ndarray) -> jnp.ndarray:
    """(B, L, n, hd) -> (B*n, L, hd)."""
    B, L, n, hd = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(B * n, L, hd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = True, return_lse: bool = False):
    """q: (B, S, nh, hd); k/v: (B, T, nkv, hd) with nh % nkv == 0.

    Returns (B, S, nh, hd), plus the per-row logsumexp (B, nh, S) fp32
    when ``return_lse`` (the backward's only extra residual).  S and T are
    padded to the block sizes internally (padded queries produce garbage
    rows that are sliced off; padded keys are masked via the length guard
    folded into the causal iota comparison).
    """
    B, S, nh, hd = q.shape
    T, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)

    bq_, bk_ = min(bq, S), min(bk, T)
    pq, pk = (-S) % bq_, (-T) % bk_
    qp, kp, vp = _pad_seq(q, pq), _pad_seq(k, pk), _pad_seq(v, pk)
    Sp, Tp = S + pq, T + pk

    qh = _heads_major(qp)                  # (B*nh, Sp, hd)
    kh = _heads_major(kp)                  # (B*nkv, Tp, hd)
    vh = _heads_major(vp)

    n_q, n_kv = Sp // bq_, Tp // bk_
    grid = (B * nh, n_q, n_kv)

    def qmap(bh, iq, ik):
        return (bh, iq, 0)

    def kvmap(bh, iq, ik):
        return (bh // g, ik, 0)

    out_specs = [pl.BlockSpec((1, bq_, hd), qmap)]
    out_shape = [jax.ShapeDtypeStruct((B * nh, Sp, hd), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, 1, bq_), lambda bh, iq, ik: (bh, 0, iq)))
        out_shape.append(jax.ShapeDtypeStruct((B * nh, 1, Sp), jnp.float32))

    res = pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=n_kv, bq=bq_, bk=bk_,
                          causal=causal, scale=scale, t_real=T,
                          with_lse=return_lse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, hd), qmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
        ],
        out_specs=out_specs if return_lse else out_specs[0],
        out_shape=out_shape if return_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = res[0] if return_lse else res
    out = out.reshape(B, nh, Sp, hd)[:, :, :S]
    out = jnp.moveaxis(out, 1, 2)
    if return_lse:
        lse = res[1].reshape(B, nh, Sp)[:, :, :S]
        return out, lse
    return out


# ---------------------------------------------------------------------------
# backward: dq (plain fp32 recompute)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                         dq_ref, acc, *, n_kv: int, bq: int, bk: int,
                         causal: bool, scale: float, s_real: int,
                         t_real: int):
    """One (bh, iq, ik) step: fold KV block ik into the dq accumulator."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    run = jnp.logical_or(not causal, ik * bk <= iq * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]                  # (bq, 1)
        dlt = dlt_ref[0, 0][:, None]
        qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = jnp.logical_and(kj < t_real, qi < s_real)
        if causal:
            valid = jnp.logical_and(valid, kj <= qi)
        p = p_tile(q, k, lse, valid, scale)
        ds = ds_tile(p, _dot_nt(do, v), dlt, scale)
        acc[...] += _dot_nn(ds, k)

    @pl.when(ik == n_kv - 1)
    def _finish():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


def flash_bwd_dq_pallas(q, k, v, do, lse, delta, *, causal: bool = True,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool = True) -> jnp.ndarray:
    """dq of flash attention, recomputed tile-by-tile — no (S, T) in HBM.

    ``lse``/``delta``: (B, nh, S) fp32 (forward logsumexp; rowsum(dO*O)).
    Returns dq (B, S, nh, hd) fp32.
    """
    B, S, nh, hd = q.shape
    T, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    bq_, bk_ = min(bq, S), min(bk, T)
    pq, pk = (-S) % bq_, (-T) % bk_
    qh = _heads_major(_pad_seq(q, pq))
    doh = _heads_major(_pad_seq(do, pq))
    kh = _heads_major(_pad_seq(k, pk))
    vh = _heads_major(_pad_seq(v, pk))
    Sp, Tp = S + pq, T + pk
    rows = jnp.pad(jnp.stack([lse, delta]), ((0, 0),) * 3 + ((0, pq),)) \
        if pq else jnp.stack([lse, delta])
    lseh = rows[0].reshape(B * nh, 1, Sp).astype(jnp.float32)
    dlth = rows[1].reshape(B * nh, 1, Sp).astype(jnp.float32)

    n_q, n_kv = Sp // bq_, Tp // bk_

    def qmap(bh, iq, ik):
        return (bh, iq, 0)

    def kvmap(bh, iq, ik):
        return (bh // g, ik, 0)

    def rowmap(bh, iq, ik):
        return (bh, 0, iq)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv=n_kv, bq=bq_, bk=bk_,
                          causal=causal, scale=scale, s_real=S, t_real=T),
        grid=(B * nh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq_, hd), qmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
            pl.BlockSpec((1, bq_, hd), qmap),
            pl.BlockSpec((1, 1, bq_), rowmap),
            pl.BlockSpec((1, 1, bq_), rowmap),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), qmap),
        out_shape=jax.ShapeDtypeStruct((B * nh, Sp, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq_, hd), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, doh, lseh, dlth)
    return jnp.moveaxis(dq.reshape(B, nh, Sp, hd)[:, :, :S], 1, 2)


# ---------------------------------------------------------------------------
# backward: dk/dv (PSG dual accumulators — predictor + full code products)
# ---------------------------------------------------------------------------


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                          sc_ref, dvm_ref, dvf_ref, dkm_ref, dkf_ref,
                          avm, avf, akm, akf, *, n_q: int, bq: int, bk: int,
                          causal: bool, scale: float, s_real: int,
                          t_real: int, lims):
    """One (bh, ikv, iq) step: fold query block iq into the four per-kv-tile
    code-product accumulators (dv/dk x predictor/full)."""
    lim_x, lim_xm, lim_g, lim_gm = lims
    ikv = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        avm[...] = jnp.zeros_like(avm)
        avf[...] = jnp.zeros_like(avf)
        akm[...] = jnp.zeros_like(akm)
        akf[...] = jnp.zeros_like(akf)

    # skip fully-future query blocks: last q row < first kv row
    run = jnp.logical_or(not causal, iq * bq + bq - 1 >= ikv * bk)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        dlt = dlt_ref[0, 0][:, None]
        s_q, s_qm = sc_ref[0, 0], sc_ref[0, 1]
        s_do, s_dom = sc_ref[0, 2], sc_ref[0, 3]
        s_ds, s_dsm = sc_ref[0, 4], sc_ref[0, 5]
        qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = ikv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = jnp.logical_and(kj < t_real, qi < s_real)
        if causal:
            valid = jnp.logical_and(valid, kj <= qi)
        p = p_tile(q, k, lse, valid, scale)
        ds = ds_tile(p, _dot_nt(do, v), dlt, scale)
        # in-tile quantization: probabilities on the fixed [0, 1] grid, the
        # rest on the per-tensor scalar grids — codes are small integers,
        # so the fp32 accumulations below are the exact code products the
        # tiled oracle recomputes (bit-identical signs).
        avm[...] += _dot_tn(codes_tile(p, 1.0 / lim_xm, lim_xm),
                            codes_tile(do, s_dom, lim_gm))
        avf[...] += _dot_tn(codes_tile(p, 1.0 / lim_x, lim_x),
                            codes_tile(do, s_do, lim_g))
        akm[...] += _dot_tn(codes_tile(ds, s_dsm, lim_gm),
                            codes_tile(q, s_qm, lim_xm))
        akf[...] += _dot_tn(codes_tile(ds, s_ds, lim_g),
                            codes_tile(q, s_q, lim_x))

    @pl.when(iq == n_q - 1)
    def _finish():
        dvm_ref[0] = avm[...]
        dvf_ref[0] = avf[...]
        dkm_ref[0] = akm[...]
        dkf_ref[0] = akf[...]


def flash_bwd_dkv_pallas(q, k, v, do, lse, delta, scales, *, lims,
                         causal: bool = True, bq: int = DEFAULT_BQ,
                         bk: int = DEFAULT_BK, interpret: bool = True):
    """Per-query-head PSG code products of the dv/dk contractions.

    ``scales``: the (6,) vector from :func:`attention_psg_scales`;
    ``lims``: static ``(lim_x, lim_x_msb, lim_g, lim_g_msb)`` code limits.
    Returns ``(dv_msb, dv_full, dk_msb, dk_full)``, each (B, T, nh, hd)
    fp32 **in code units** and per *query* head — the caller group-sums
    over each GQA group and applies :func:`psg_attention_select`.
    """
    B, S, nh, hd = q.shape
    T, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    bq_, bk_ = min(bq, S), min(bk, T)
    pq, pk = (-S) % bq_, (-T) % bk_
    qh = _heads_major(_pad_seq(q, pq))
    doh = _heads_major(_pad_seq(do, pq))
    kh = _heads_major(_pad_seq(k, pk))
    vh = _heads_major(_pad_seq(v, pk))
    Sp, Tp = S + pq, T + pk
    rows = jnp.pad(jnp.stack([lse, delta]), ((0, 0),) * 3 + ((0, pq),)) \
        if pq else jnp.stack([lse, delta])
    lseh = rows[0].reshape(B * nh, 1, Sp).astype(jnp.float32)
    dlth = rows[1].reshape(B * nh, 1, Sp).astype(jnp.float32)
    sc = scales.reshape(1, 6).astype(jnp.float32)

    n_q, n_kv = Sp // bq_, Tp // bk_

    def qmap(bh, ikv, iq):
        return (bh, iq, 0)

    def kvmap(bh, ikv, iq):
        return (bh // g, ikv, 0)

    def rowmap(bh, ikv, iq):
        return (bh, 0, iq)

    def outmap(bh, ikv, iq):
        return (bh, ikv, 0)

    out_spec = pl.BlockSpec((1, bk_, hd), outmap)
    out_sh = jax.ShapeDtypeStruct((B * nh, Tp, hd), jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=n_q, bq=bq_, bk=bk_,
                          causal=causal, scale=scale, s_real=S, t_real=T,
                          lims=lims),
        grid=(B * nh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, bq_, hd), qmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
            pl.BlockSpec((1, bk_, hd), kvmap),
            pl.BlockSpec((1, bq_, hd), qmap),
            pl.BlockSpec((1, 1, bq_), rowmap),
            pl.BlockSpec((1, 1, bq_), rowmap),
            pl.BlockSpec((1, 6), lambda bh, ikv, iq: (0, 0)),
        ],
        out_specs=[out_spec] * 4,
        out_shape=[out_sh] * 4,
        scratch_shapes=[pltpu.VMEM((bk_, hd), jnp.float32)] * 4,
        interpret=interpret,
    )(qh, kh, vh, doh, lseh, dlth, sc)
    return tuple(jnp.moveaxis(o.reshape(B, nh, Tp, hd)[:, :, :T], 1, 2)
                 for o in outs)
