"""MobileNetV2 under E2-Train: the PSG depthwise kernel against its oracle,
the SLU gate on the identity blocks, and one training step of the program
against the benchmark's plain reference (``bench/reference/mbv2.py``)."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from repro.core import psg  # noqa: E402
from repro.core.quant import quantize  # noqa: E402
from repro.core.config import E2TrainConfig, PSGConfig, SLUConfig  # noqa: E402
from repro.kernels import dispatch, ops, ref  # noqa: E402
from repro.kernels import psg_depthwise as D  # noqa: E402
from repro.models import resnet as R  # noqa: E402

CFG = PSGConfig(enabled=True, swa=False)


# ---------------------------------------------------------------------------
# the PSG depthwise kernel
# ---------------------------------------------------------------------------


def _dw_data(b, hw, c, stride, seed=0):
    kx, kg = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.nn.relu6(2.0 * jax.random.normal(kx, (b, hw, hw, c)))
    ho = -(-hw // stride)
    return x, jax.random.normal(kg, (b, ho, ho, c))


@pytest.mark.parametrize("b,hw,c,stride", [
    (2, 9, 200, 1),     # C not a multiple of 128: one channel tile of 200
    (2, 9, 144, 2),     # stride 2, odd extent: 5x5 outputs
    (2, 6, 256, 1),     # two 128-lane channel tiles
])
def test_kernel_signs_match_the_oracle_bit_for_bit(b, hw, c, stride):
    """The interpreted kernel's signs and fallback share equal the
    element-level oracle's.  The MSB product is exact at these sizes, so
    the select sees the same entries; full-product sums differ only in
    f32 summation order, far from zero here."""
    x, gy = _dw_data(b, hw, c, stride)
    sign, fb = ops.psg_depthwise_grad_w(x, gy, CFG, stride, interpret=True)
    want, fb_want = ref.psg_depthwise_grad_w_ref(x, gy, CFG, stride)
    assert sign.shape == (9, c)
    assert np.array_equal(np.asarray(sign), np.asarray(want))
    assert float(fb) == float(fb_want)
    assert 0.0 < float(fb) < 1.0


@pytest.mark.parametrize("stride", [1, 2])
def test_row_tiles_that_do_not_divide_the_rows(stride):
    """M = 2 * 10 * 10 (or 2 * 5 * 5) rows in row tiles of 32: the padded
    rows add zero codes, and the products equal plain sums exactly (the
    code products and their sums stay under 2^24 here)."""
    x, gy = _dw_data(2, 10, 40, stride, seed=3)
    codes = [D.depthwise_taps(ops._codes(x, bits)[0], stride)
             for bits in (CFG.bits_x_msb, CFG.bits_x)]
    g2 = gy.reshape(-1, 40)
    gcodes = [ops._codes(g2, bits)[0] for bits in (CFG.bits_g_msb, 8)]
    assert codes[0].shape[1] % 32 != 0
    got = D.psg_depthwise_products(codes[0], gcodes[0], codes[1], gcodes[1],
                                   bm=32)
    for prod, xc, gc in zip(got, codes, gcodes):
        want = jnp.sum(xc.astype(jnp.float32)
                       * gc.astype(jnp.float32)[None], axis=1)
        assert np.array_equal(np.asarray(prod), np.asarray(want))


def test_every_backend_routes_the_same_semantics():
    x, gy = _dw_data(2, 8, 96, 2, seed=5)
    with dispatch.override_backend("reference"):
        a = dispatch.psg_depthwise_grad_w(x, gy, CFG, stride=2)
    with dispatch.override_backend("interpret"):
        b = dispatch.psg_depthwise_grad_w(x, gy, CFG, stride=2)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert float(a[1]) == float(b[1])


def test_psg_depthwise_vjp_and_probe():
    """Forward on the bits_x grid; the input gradient is the transposed
    depthwise conv of the bits_g output gradient; the weight gradient is
    the kernel's sign; the probe's depthwise slots count (9, C) entries
    and leave the matmul slots alone."""
    x, gy = _dw_data(2, 8, 64, 2, seed=7)
    w = jax.random.normal(jax.random.PRNGKey(8), (9, 64)) / 3.0

    def loss(x_, w_, probe):
        with psg.enable(CFG, probe=probe):
            return jnp.sum(R._depthwise(w_, x_, 2) * gy)

    y = R._depthwise(quantize(w, 8), quantize(x, 8), 2)
    with psg.enable(CFG):
        np.testing.assert_allclose(R._depthwise(w, x, 2), y, rtol=1e-6)
    dx, dw, dprobe = jax.grad(loss, argnums=(0, 1, 2))(x, w,
                                                       psg.zero_probe())
    sign, fb = ref.psg_depthwise_grad_w_ref(x, gy, CFG, 2)
    assert np.array_equal(np.asarray(dw), np.asarray(sign))
    _, vjp = jax.vjp(lambda a: R._depthwise(quantize(w, 8), a, 2), x)
    np.testing.assert_allclose(dx, vjp(quantize(gy, 16))[0], rtol=1e-6,
                               atol=1e-6)
    assert float(dprobe[3]) == 9 * 64 and float(dprobe[0]) == 0.0
    assert float(psg.probe_dw_fallback_ratio(dprobe)) == pytest.approx(
        float(fb))


# ---------------------------------------------------------------------------
# SLU on the identity blocks
# ---------------------------------------------------------------------------


def _forward_before_slu(p, state, x, train=True):
    """MobileNetV2's forward as it was before the SLU gate: every block
    runs; the anchor for the SLU-off and eval paths."""
    h, _ = R.batchnorm(p["stem_bn"], state["stem_bn"], R.conv2d(p["stem"], x),
                       train)
    h = jax.nn.relu6(h)
    for blk, bst, (_c, _h, _o, stride, residual) in zip(
            p["blocks"], state["blocks"], R._mbv2_layout()):
        y, _ = R.batchnorm(blk["bn1"], bst["bn1"],
                           R.conv2d(blk["expand"], h, k=1), train)
        y = R._depthwise(blk["dw"], jax.nn.relu6(y), stride)
        y, _ = R.batchnorm(blk["bn2"], bst["bn2"], y, train)
        y, _ = R.batchnorm(blk["bn3"], bst["bn3"],
                           R.conv2d(blk["project"], jax.nn.relu6(y), k=1),
                           train)
        h = h + y if residual else y
    h, _ = R.batchnorm(p["head_bn"], state["head_bn"],
                       R.conv2d(p["head"], h, k=1), train)
    return jnp.mean(jax.nn.relu6(h), axis=(1, 2)) @ p["fc_w"] + p["fc_b"]


@pytest.fixture(scope="module")
def gated_model():
    e2 = E2TrainConfig(slu=SLUConfig(enabled=True))
    p, s = R.init_mobilenetv2(jax.random.PRNGKey(0), e2=e2)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
    return e2, p, s, x


def test_slu_gates_exactly_the_ten_identity_blocks(gated_model):
    e2, p, s, x = gated_model
    gated = [i for i, blk in enumerate(R._mbv2_layout()) if blk[4]]
    assert gated == [2, 4, 5, 7, 8, 9, 11, 12, 14, 15]
    assert R.mbv2_gate_width() == 160
    assert p["slu_gate"]["proj"].shape == (160, e2.slu.gate_proj)
    fwd = jax.jit(lambda p, s, x, r: R.mobilenetv2_fwd(p, s, x, e2, r)[1])
    seen = np.zeros(17)
    for seed in range(6):
        aux = fwd(p, s, x, jax.random.PRNGKey(seed))
        ex = np.asarray(aux["slu_executed"])
        kp = np.asarray(aux["slu_keep_probs"])
        ungated = [i for i in range(17) if i not in gated]
        assert np.all(ex[ungated] == 1.0) and np.all(kp[ungated] == 1.0)
        assert ex[2] == 1.0 and ex[15] == 1.0      # never_skip_first_last
        seen += 1.0 - ex
        assert float(aux["slu_cost"]) == pytest.approx(kp.mean())
    assert seen[[4, 5, 7, 8, 9, 11, 12, 14]].sum() > 0


def test_slu_off_and_eval_equal_the_ungated_forward_bit_for_bit(gated_model):
    e2, p, s, x = gated_model
    plain, _ = R.init_mobilenetv2(jax.random.PRNGKey(0))
    assert jax.tree.structure(plain) != jax.tree.structure(p)
    before = jax.jit(_forward_before_slu, static_argnums=3)
    fwd = jax.jit(lambda p, s, x, r, train: R.mobilenetv2_fwd(
        p, s, x, e2 if "slu_gate" in p else None, r, train=train)[:2],
        static_argnums=4)
    for train in (True, False):
        off, aux = fwd(plain, s, x, jax.random.PRNGKey(3), train)
        assert np.array_equal(np.asarray(off),
                              np.asarray(before(plain, s, x, train)))
        assert float(aux["slu_cost"]) == 1.0
    on_eval, aux = fwd(p, s, x, jax.random.PRNGKey(3), False)
    assert np.array_equal(np.asarray(on_eval),
                          np.asarray(before(p, s, x, False)))
    assert np.all(np.asarray(aux["slu_executed"]) == 1.0)


# ---------------------------------------------------------------------------
# one training step against the plain reference
# ---------------------------------------------------------------------------

WEIGHTS_SEED = 11


def _reference_step(image_hw):
    """The benchmark's seeded weights and batch at ``image_hw`` images,
    batch 4, widths unchanged, and one E2-Train step of the plain
    reference from them."""
    from bench.families import cifar_mbv2 as F
    from bench.reference import mbv2 as M
    from bench.traffic import make_batches
    cfg = json.loads((REPO / "bench/configs/mobilenetv2.json").read_text())
    cfg["arch"]["image_hw"] = image_hw
    mix = json.loads((REPO / "bench/mixes/e2train.json").read_text())
    mix["batch_per_chip"] = 4
    exp = F.program_experiment(cfg, mix, 1, 0)
    start = jax.device_get(F.init_state(cfg, exp, WEIGHTS_SEED))
    batch = make_batches(mix["data"], cfg["arch"], 5, 4)(0, 0)
    with jax.default_matmul_precision("highest"):
        train = F.train_settings(cfg, mix, exp.train.seed)
        p, _, bn, outs = M.run_steps(
            cfg["arch"], mix["e2train"], train, start.params,
            F.optimizer_buffer(start.opt), start.model_state, [batch], [0])
    refd = {"loss": float(outs[0]["loss"]),
            "executed": np.asarray(outs[0]["executed"]), "params": p,
            "state": bn}
    return exp, start, batch, refd


@pytest.fixture(scope="module")
def reference_step():
    """At the cell's 32x32 images: BatchNorm sees at least 4 x 4 x 4
    values in every layer."""
    return _reference_step(32)


@pytest.fixture(scope="module")
def reference_step_8x8():
    """At 8x8 images: the last stages' BatchNorm sees 4 x 1 x 1 values."""
    return _reference_step(8)


def _step_pair(reference_step, fused):
    """(program, reference, start) of one E2-Train step from the same
    weights and batch; ``fused`` is the program's ``fused_conv``."""
    from repro.training.train_step import make_train_step
    exp, start, batch, refd = reference_step
    exp = dataclasses.replace(exp, e2=dataclasses.replace(
        exp.e2, psg=dataclasses.replace(exp.e2.psg, fused_conv=fused)))
    assert psg.fused_conv_active(exp.e2.psg) == (fused is None)
    with jax.default_matmul_precision("highest"):
        new, metrics = jax.jit(make_train_step(exp))(start, batch)
    prog = {"loss": float(metrics["total_loss"]),
            "exec_ratio": float(metrics["slu_exec_ratio"]),
            "params": new.params, "state": new.model_state,
            "dw_fallback": float(metrics["psg_dw_fallback_ratio"])}
    return prog, refd, start


def _ran(state, start):
    """Per block, whether its BatchNorm statistics moved: a block SLU
    skipped leaves them as they were."""
    return np.array([not np.array_equal(np.asarray(a["bn1"]["mean"]),
                                        np.asarray(b["bn1"]["mean"]))
                     for a, b in zip(state["blocks"],
                                     start.model_state["blocks"])])


def _moves_agree(prog, refd, start):
    """Per parameter, whether the program's sign-SGD move equals the
    reference's: each moves by lr * (sign(g) + wd * p), so a flipped sign
    moves it 2 lr = 0.06 the other way."""
    return jax.tree.map(
        lambda a, b, p0: np.isclose(np.asarray(a) - p0, np.asarray(b) - p0,
                                    atol=1e-4),
        prog["params"], refd["params"], start.params)


def _slu_agrees(prog, refd, start):
    executed = refd["executed"]
    assert 0 < executed.sum() < 17
    assert np.array_equal(_ran(prog["state"], start), executed == 1.0)
    assert np.array_equal(_ran(refd["state"], start), executed == 1.0)
    assert prog["exec_ratio"] == pytest.approx(executed.mean())


def test_one_step_matches_the_reference_on_the_im2col_path(reference_step):
    """``fused_conv=False``, the conv path the chip runs, at the cell's
    image size: the program and the reference run the same f32
    operations on the CPU, so the loss, the SLU decisions, every sign
    gradient and the BatchNorm statistics agree to f32 rounding (1e-6
    relative: XLA may fuse the two sides' reductions differently)."""
    prog, refd, start = _step_pair(reference_step, False)
    assert prog["loss"] == pytest.approx(refd["loss"], rel=1e-6)
    _slu_agrees(prog, refd, start)
    assert all(np.all(a) for a in jax.tree.leaves(
        _moves_agree(prog, refd, start)))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), prog["state"], refd["state"])
    assert 0.0 < prog["dw_fallback"] < 1.0


def test_one_step_matches_the_reference_on_the_fused_default_path(
        reference_step_8x8):
    """The CPU default (``fused_conv`` None: the implicit-GEMM conv
    kernels) sums the stem and 1x1 convs in another f32 order than the
    reference's im2col matmuls.  The SLU decisions still agree exactly
    (the gates pool whole block inputs), and so do the stem's BatchNorm
    statistics to f32 rounding.  Deeper down, PSG's 8-bit forward turns
    some of those roundings into whole code steps that 17 blocks of
    BatchNorm over 4 x 1 x 1 values amplify: the loss is held to 25%,
    and at least 80% of the parameters must move with the reference's
    sign (a dropped or misrouted gradient moves about half)."""
    prog, refd, start = _step_pair(reference_step_8x8, None)
    _slu_agrees(prog, refd, start)
    for k in ("mean", "var"):
        np.testing.assert_allclose(prog["state"]["stem_bn"][k],
                                   refd["state"]["stem_bn"][k], rtol=1e-5,
                                   atol=1e-6)
    assert prog["loss"] == pytest.approx(refd["loss"], rel=0.25)
    agree = jax.tree.leaves(_moves_agree(prog, refd, start))
    share = sum(np.sum(a) for a in agree) / sum(np.size(a) for a in agree)
    assert share >= 0.8
