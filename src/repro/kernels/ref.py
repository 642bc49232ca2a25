"""Pure-jnp oracles for the Pallas kernels (the ``ref.py`` contract).

Every kernel in this package is validated against these references in
``tests/test_kernels.py`` over a shape/dtype sweep.  Since the dispatch
layer landed (DESIGN.md §Dispatch) the element-level PSG weight-gradient
lives HERE, as a test-only reference: the training hot path runs the
tile-level kernel (``kernels/ops.psg_grad_w``), and these oracles are what
it is held accountable to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.config import PSGConfig
from repro.core.quant import msb_of, quantize, quantize_int


def quantize_ref(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Fake-quantization oracle (matches kernels/quant.py)."""
    return quantize(x, bits)


def predictor_confidence_ref(x2: jnp.ndarray, gy2: jnp.ndarray,
                             cfg: PSGConfig
                             ) -> tuple:
    """Eq. (2)'s predictor state, computed once: (g_msb, confident_mask).

    The single definition of the MSB product + adaptive threshold
    ``tau = beta * max|g_msb|`` — the sign oracle, the fallback-ratio
    reference and ``core/psg.psg_predictor_usage`` all derive from this so
    a threshold-rule change cannot desynchronize them.
    """
    xm = msb_of(x2, cfg.bits_x, cfg.bits_x_msb)
    gm = msb_of(gy2, cfg.bits_g, cfg.bits_g_msb)
    g_msb = xm.astype(jnp.float32).T @ gm.astype(jnp.float32)
    tau = cfg.beta * jnp.max(jnp.abs(g_msb))
    return g_msb, jnp.abs(g_msb) >= tau


def psg_grad_w_ref(x2: jnp.ndarray, gy2: jnp.ndarray, cfg: PSGConfig
                   ) -> jnp.ndarray:
    """Element-level Eq. (2).  x2: (N, din), gy2: (N, dout) -> (din, dout).

    Returns the sign-valued weight gradient in {-1, 0, +1} (float32).
    The paper's rule: use sign(g_msb) where the MSB predictor's magnitude
    clears the adaptive threshold; fall back to the sign of the full
    fixed-point product elsewhere.
    """
    xq = quantize(x2, cfg.bits_x)
    gq = quantize(gy2, cfg.bits_g)
    g_full = xq.astype(jnp.float32).T @ gq.astype(jnp.float32)
    g_msb, pred_ok = predictor_confidence_ref(x2, gy2, cfg)
    return jnp.where(pred_ok, jnp.sign(g_msb), jnp.sign(g_full))


def psg_fallback_ratio_ref(x2: jnp.ndarray, gy2: jnp.ndarray, cfg: PSGConfig
                           ) -> jnp.ndarray:
    """Element-level fallback fraction: entries the predictor could NOT
    decide (the complement of the paper's §4.4 predictor-usage figure).
    The tile-level kernel reports the analogous *tile* ratio."""
    _, pred_ok = predictor_confidence_ref(x2, gy2, cfg)
    return jnp.mean(jnp.logical_not(pred_ok).astype(jnp.float32))


# ---------------------------------------------------------------------------
# conv oracles: materialized im2col — what the implicit-GEMM kernels
# (kernels/conv.py) eliminate and are held accountable to
# ---------------------------------------------------------------------------


def conv_patches_ref(xp: jnp.ndarray, k: int, stride: int) -> jnp.ndarray:
    """Materialized im2col of a pre-padded NHWC input: ``(B*Ho*Wo, k*k*C)``
    in the patch-major (channel-major) layout the model weights use."""
    p = jax.lax.conv_general_dilated_patches(
        xp, (k, k), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return p.reshape(-1, p.shape[-1])


def conv_fwd_ref(xp: jnp.ndarray, w: jnp.ndarray, k: int, stride: int
                 ) -> jnp.ndarray:
    """im2col + single-GEMM conv forward (the materialized reference)."""
    B, Hp, Wp, _ = xp.shape
    ho = (Hp - k) // stride + 1
    wo = (Wp - k) // stride + 1
    y = conv_patches_ref(xp, k, stride) @ w.astype(xp.dtype)
    return y.reshape(B, ho, wo, -1)


def conv_grad_x_ref(gq: jnp.ndarray, wq: jnp.ndarray, k: int, stride: int,
                    hp: int, wp: int) -> jnp.ndarray:
    """Per-tap col2im scatter-add input gradient — the demoted reference
    the implicit transposed-conv kernel (``kernels/conv.py``) is held to.

    Each tap's ``(B*Ho*Wo, C)`` contribution is computed and scattered
    into a strided window of the full-size accumulator: k^2 strided
    read-modify-write passes, the traffic pattern the kernel eliminates.
    Accumulation is forced to float32 regardless of the operand dtype
    (accumulating ``k^2`` taps in a narrow gradient dtype loses low-order
    contributions; ``tests/test_conv.py`` pins the regression).
    """
    from repro.kernels.conv import to_tap_major
    B, ho, wo, dout = gq.shape
    C = wq.shape[0] // (k * k)
    wt = to_tap_major(wq.astype(jnp.float32), k, C)
    g2 = gq.astype(jnp.float32).reshape(-1, dout)
    dx = jnp.zeros((B, hp, wp, C), jnp.float32)
    for t in range(k * k):
        ki, kj = t // k, t % k
        g_t = (g2 @ wt[t * C:(t + 1) * C, :].T).reshape(B, ho, wo, C)
        dx = dx.at[:, ki:ki + (ho - 1) * stride + 1:stride,
                   kj:kj + (wo - 1) * stride + 1:stride, :].add(g_t)
    return dx


def conv_grad_w_ref(xp: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                    k: int, stride: int) -> jnp.ndarray:
    """Element-level PSG conv weight gradient: materialize the im2col
    operand, then apply the Eq. (2) oracle — ``(k*k*C, dout)`` signs."""
    p2 = conv_patches_ref(xp, k, stride)
    return psg_grad_w_ref(p2, gy.reshape(-1, gy.shape[-1]), cfg)


def conv_fallback_ratio_ref(xp: jnp.ndarray, gy: jnp.ndarray, cfg: PSGConfig,
                            k: int, stride: int) -> jnp.ndarray:
    """Element-level fallback fraction over the im2col operand."""
    p2 = conv_patches_ref(xp, k, stride)
    return psg_fallback_ratio_ref(p2, gy.reshape(-1, gy.shape[-1]), cfg)


# ---------------------------------------------------------------------------
# depthwise oracle (kernels/psg_depthwise.py)
# ---------------------------------------------------------------------------


def psg_depthwise_grad_w_ref(x: jnp.ndarray, gy: jnp.ndarray,
                             cfg: PSGConfig, stride: int) -> tuple:
    """Element-level Eq. (2) of a depthwise weight gradient: ``(sign (9, C)
    float32, fallback share of entries below tau)``.  Both products in
    code units, ``sum_m codes(P_t)[m, c] * codes(gy)[m, c]``, the codes
    made on ``x`` before the windows are gathered."""
    from repro.kernels.psg_depthwise import depthwise_taps
    g2 = gy.reshape(-1, x.shape[-1])

    def codes(a, bits):
        return quantize_int(a, bits)[0].astype(jnp.float32)

    def product(bx, bg):
        taps = depthwise_taps(codes(x, bx), stride)
        return jnp.sum(taps * codes(g2, bg)[None], axis=1)

    g_msb = product(cfg.bits_x_msb, cfg.bits_g_msb)
    g_full = product(cfg.bits_x, cfg.bits_g)
    tau = cfg.beta * jnp.max(jnp.abs(g_msb))
    conf = jnp.abs(g_msb) >= tau
    return (jnp.where(conf, jnp.sign(g_msb), jnp.sign(g_full)),
            jnp.mean(jnp.logical_not(conf).astype(jnp.float32)))


def psg_grad_w_oracle(x2: jnp.ndarray, gy2: jnp.ndarray, cfg: PSGConfig
                      ) -> jnp.ndarray:
    """Element-level Eq. (2) — identical semantics to the tile-level kernel:
    a tile that is fully predictor-confident emits sign(g_msb) (== the
    element-level choice for those entries); any other tile computes the full
    product and uses it exactly where the element-level rule would."""
    return psg_grad_w_ref(x2, gy2, cfg)


def predictor_matmul_oracle(x2: jnp.ndarray, gy2: jnp.ndarray,
                            cfg: PSGConfig) -> jnp.ndarray:
    """The MSB predictor product g_msb = (x_msb)^T (gy_msb), fp32."""
    xm = msb_of(x2, cfg.bits_x, cfg.bits_x_msb)
    gm = msb_of(gy2, cfg.bits_g, cfg.bits_g_msb)
    return xm.astype(jnp.float32).T @ gm.astype(jnp.float32)


def flash_attention_oracle(q, gk, gv, causal: bool = True):
    """Pure-jnp softmax attention (GQA), fp32 — oracle for flash_attn.py."""
    import math
    B, S, nh, hd = q.shape
    T, nkv = gk.shape[1], gk.shape[2]
    g = nh // nkv
    qf = q.reshape(B, S, nkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bsngh,btnh->bnsgt", qf,
                   gk.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        m = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(m[None, None, :, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnsgt,btnh->bsngh", w, gv.astype(jnp.float32))
    return o.reshape(B, S, nh, hd)


# ---------------------------------------------------------------------------
# attention backward oracles (flash_attn.py's recomputed-tile kernels)
# ---------------------------------------------------------------------------


def _attn_scores_ref(q, gk, causal: bool):
    """Masked fp32 scores per query head, (B, nkv, S, g, T)."""
    import math
    B, S, nh, hd = q.shape
    T, nkv = gk.shape[1], gk.shape[2]
    g = nh // nkv
    qf = q.reshape(B, S, nkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bsngh,btnh->bnsgt", qf,
                   gk.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        m = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(m[None, None, :, None, :], s, -1e30)
    return s


def attention_lse_ref(q, gk, causal: bool = True) -> jnp.ndarray:
    """Per-row logsumexp of the masked scores, (B, nh, S) fp32 — the
    residual the flash forward emits with ``return_lse=True``."""
    s = _attn_scores_ref(q, gk, causal)            # (B, nkv, S, g, T)
    lse = jax.nn.logsumexp(s, axis=-1)             # (B, nkv, S, g)
    B, nkv, S, g = lse.shape
    return jnp.moveaxis(lse, 2, 3).reshape(B, nkv * g, S)


def flash_attention_vjp_oracle(q, gk, gv, do, causal: bool = True):
    """fp32 (dq, dk, dv) — plain autodiff of the materialized oracle."""
    f = lambda a, b, c: flash_attention_oracle(a, b, c, causal)  # noqa: E731
    _, vjp = jax.vjp(f, q.astype(jnp.float32), gk.astype(jnp.float32),
                     gv.astype(jnp.float32))
    return vjp(do.astype(jnp.float32))


def psg_attention_bwd_ref(q, gk, gv, do, cfg: PSGConfig,
                          causal: bool = True):
    """Element-level PSG attention backward — the reference-backend path.

    dq is the exact fp32 cotangent (no PSG there, matching the kernel
    path).  dk/dv apply Eq. (2) at element level on the materialized
    probability/dS tensors: quantize each operand onto the same grids the
    kernel uses (``flash_attn.attention_psg_scales``), form the MSB and
    full code products per *query* head, sum each GQA group, then the
    shared select picks predictor values where confident.  Returns
    ``(dq, dk, dv, fallback_ratio)``.
    """
    import math

    from repro.kernels import flash_attn as fa
    B, S, nh, hd = q.shape
    T, nkv = gk.shape[1], gk.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    dq, _, _ = flash_attention_vjp_oracle(q, gk, gv, do, causal)

    s = _attn_scores_ref(q, gk, causal)
    p = jax.nn.softmax(s, axis=-1)                 # (B, nkv, S, g, T)
    do_r = do.reshape(B, S, nkv, g, hd).astype(jnp.float32)
    dp = jnp.einsum("bsngh,btnh->bnsgt", do_r, gv.astype(jnp.float32))
    o = jnp.einsum("bnsgt,btnh->bsngh", p, gv.astype(jnp.float32))
    delta = jnp.sum(do_r * o, axis=-1)             # (B, S, nkv, g)
    ds = p * (dp - jnp.moveaxis(delta, 1, 2)[..., None]) * scale

    dlt_rows = jnp.moveaxis(delta.reshape(B, S, nh), 1, 2)  # (B, nh, S)
    scales = fa.attention_psg_scales(
        q, gv, do, dlt_rows, bits_x=cfg.bits_x, bits_x_msb=cfg.bits_x_msb,
        bits_g=cfg.bits_g, bits_g_msb=cfg.bits_g_msb)
    s_q, s_qm, s_do, s_dom, s_ds, s_dsm = scales
    lim_x, lim_xm = fa.qlim(cfg.bits_x), fa.qlim(cfg.bits_x_msb)
    lim_g, lim_gm = fa.qlim(cfg.bits_g), fa.qlim(cfg.bits_g_msb)
    q_r = q.reshape(B, S, nkv, g, hd).astype(jnp.float32)

    # code products summed over (s, group) jointly == group-summed
    # per-query-head products; the select then operates on kv-head tensors
    dv_m = jnp.einsum("bnsgt,bsngd->btnd",
                      fa.codes_tile(p, 1.0 / lim_xm, lim_xm),
                      fa.codes_tile(do_r, s_dom, lim_gm))
    dv_f = jnp.einsum("bnsgt,bsngd->btnd",
                      fa.codes_tile(p, 1.0 / lim_x, lim_x),
                      fa.codes_tile(do_r, s_do, lim_g))
    dk_m = jnp.einsum("bnsgt,bsngd->btnd",
                      fa.codes_tile(ds, s_dsm, lim_gm),
                      fa.codes_tile(q_r, s_qm, lim_xm))
    dk_f = jnp.einsum("bnsgt,bsngd->btnd",
                      fa.codes_tile(ds, s_ds, lim_g),
                      fa.codes_tile(q_r, s_q, lim_x))
    dv, r_dv = fa.psg_attention_select(dv_m, dv_f, (1.0 / lim_xm) * s_dom,
                                       (1.0 / lim_x) * s_do, cfg.beta)
    dk, r_dk = fa.psg_attention_select(dk_m, dk_f, s_dsm * s_qm,
                                       s_ds * s_q, cfg.beta)
    return dq, dk, dv, 0.5 * (r_dv + r_dk)


def attention_dkv_products_oracle(q, gk, gv, do, lse, delta, scales, *,
                                  lims, causal: bool = True,
                                  bq: int | None = None,
                                  bk: int | None = None):
    """Tile-replay oracle of ``flash_bwd_dkv_pallas``'s code products.

    Recomputes the four per-query-head code-product accumulators with a
    plain Python loop over the SAME tile schedule — identical block
    shapes, identical ``lax.dot_general`` calls (the shared tile helpers
    in flash_attn.py), identical accumulation order — so the fp32 results
    are bit-identical to the kernel's, which is what pins the dv/dk sign
    agreement.  Returns ``(dv_msb, dv_full, dk_msb, dk_full)``, each
    (B, T, nh, hd) fp32 in code units.
    """
    import math

    from repro.kernels import flash_attn as fa
    B, S, nh, hd = q.shape
    T, nkv = gk.shape[1], gk.shape[2]
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    bq_ = min(fa.DEFAULT_BQ if bq is None else bq, S)
    bk_ = min(fa.DEFAULT_BK if bk is None else bk, T)
    pq, pk = (-S) % bq_, (-T) % bk_
    qh = fa._heads_major(fa._pad_seq(q, pq))
    doh = fa._heads_major(fa._pad_seq(do, pq))
    kh = fa._heads_major(fa._pad_seq(gk, pk))
    vh = fa._heads_major(fa._pad_seq(gv, pk))
    Sp, Tp = S + pq, T + pk
    rows = jnp.pad(jnp.stack([lse, delta]), ((0, 0),) * 3 + ((0, pq),)) \
        if pq else jnp.stack([lse, delta])
    lseh = rows[0].reshape(B * nh, Sp).astype(jnp.float32)
    dlth = rows[1].reshape(B * nh, Sp).astype(jnp.float32)
    s_q, s_qm, s_do, s_dom, s_ds, s_dsm = scales.astype(jnp.float32)
    lim_x, lim_xm, lim_g, lim_gm = lims
    n_q, n_kv = Sp // bq_, Tp // bk_

    outs = [jnp.zeros((B * nh, Tp, hd), jnp.float32) for _ in range(4)]
    for bh in range(B * nh):
        for ikv in range(n_kv):
            accs = [jnp.zeros((bk_, hd), jnp.float32) for _ in range(4)]
            kt = kh[bh // g, ikv * bk_:(ikv + 1) * bk_].astype(jnp.float32)
            vt = vh[bh // g, ikv * bk_:(ikv + 1) * bk_].astype(jnp.float32)
            for iq in range(n_q):
                if causal and not (iq * bq_ + bq_ - 1 >= ikv * bk_):
                    continue
                qt = qh[bh, iq * bq_:(iq + 1) * bq_].astype(jnp.float32)
                dot = doh[bh, iq * bq_:(iq + 1) * bq_].astype(jnp.float32)
                lse_t = lseh[bh, iq * bq_:(iq + 1) * bq_][:, None]
                dlt_t = dlth[bh, iq * bq_:(iq + 1) * bq_][:, None]
                qi = iq * bq_ + jax.lax.broadcasted_iota(
                    jnp.int32, (bq_, bk_), 0)
                kj = ikv * bk_ + jax.lax.broadcasted_iota(
                    jnp.int32, (bq_, bk_), 1)
                valid = jnp.logical_and(kj < T, qi < S)
                if causal:
                    valid = jnp.logical_and(valid, kj <= qi)
                p = fa.p_tile(qt, kt, lse_t, valid, scale)
                ds = fa.ds_tile(p, fa._dot_nt(dot, vt), dlt_t, scale)
                accs[0] += fa._dot_tn(fa.codes_tile(p, 1.0 / lim_xm, lim_xm),
                                      fa.codes_tile(dot, s_dom, lim_gm))
                accs[1] += fa._dot_tn(fa.codes_tile(p, 1.0 / lim_x, lim_x),
                                      fa.codes_tile(dot, s_do, lim_g))
                accs[2] += fa._dot_tn(fa.codes_tile(ds, s_dsm, lim_gm),
                                      fa.codes_tile(qt, s_qm, lim_xm))
                accs[3] += fa._dot_tn(fa.codes_tile(ds, s_ds, lim_g),
                                      fa.codes_tile(qt, s_q, lim_x))
            for i in range(4):
                outs[i] = outs[i].at[bh, ikv * bk_:(ikv + 1) * bk_].set(
                    accs[i])
    return tuple(jnp.moveaxis(o.reshape(B, nh, Tp, hd)[:, :, :T], 1, 2)
                 for o in outs)
