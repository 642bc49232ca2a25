"""The ``mobilenetv2`` configuration and its cell: the pinned arithmetic,
the PSG depthwise readers on a hand-built trace, and ``correct`` on a
tiny MobileNetV2 cell under the real cell's limits."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

from bench import calibrate  # noqa: E402
from bench import correct as C  # noqa: E402
from bench import spec as S  # noqa: E402
from bench import trace as T  # noqa: E402
from test_bench_trace import Ev, host, tpu  # noqa: E402

REPO = bench_tiny.REPO
CELL = "mobilenetv2.e2train"


@pytest.fixture(scope="module")
def bench():
    return S.Benchmark(REPO)


def test_the_config_pins_the_familys_arithmetic(bench):
    cfg = bench.cell(CELL).config
    fam = bench.family(cfg["family"])
    arch = cfg["arch"]
    assert cfg["reduced"] == []
    assert fam.flop_per_image(arch) == cfg["flop_per_image"]
    assert fam.param_count(arch) == cfg["param_count"]
    assert len(fam.psg_sites(arch, 128)) == 36
    dws = fam.dw_sites(arch)
    assert len(dws) == 17
    assert sum(d["gated"] for d in dws) == 10
    assert [d["stride"] for d in dws].count(2) == 3
    assert max(d["C"] for d in dws) == 960
    # 9 taps per output element: 5.88 M per image, as the layout says
    assert sum(9 * d["out_hw"] ** 2 * d["C"] for d in dws) == 5879808


def test_the_programs_backbone_has_the_pinned_parameters(bench):
    """The program's MobileNetV2, SLU off, holds ``param_count``
    parameters; the cell's weights add the SLU gate."""
    import jax
    import numpy as np
    from repro.configs.paper_cnns import mobilenetv2
    from repro.training.train_step import init_train_state
    cell = bench.cell(CELL)
    shapes = jax.eval_shape(lambda k: init_train_state(k, mobilenetv2()),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes.params))
    assert count == cell.config["param_count"]
    fam = bench.family("cifar_mbv2")
    exp = fam.program_experiment(cell.config, cell.mix, 1, 0)
    assert exp.model.name == "mobilenetv2" and exp.e2.slu.enabled
    shapes = jax.eval_shape(lambda k: init_train_state(k, exp),
                            jax.random.PRNGKey(0))
    assert shapes.params["slu_gate"]["proj"].shape == (160, 10)


def test_the_cell_reports_what_every_cell_reports(bench):
    cell = bench.cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "e2train"
    assert {m["name"] for m in cell.per_layer} >= {
        "device_idle_share", "step_mfu"}
    assert set(cell.limits["limits"]) >= {"loss_first", "smd_steps"}


# ---------------------------------------------------------------------------
# the PSG depthwise readers
# ---------------------------------------------------------------------------

# the stride-1 depthwise conv at 32x32 with 144 channels, batch 128: taps
# and output-gradient codes, as a TPU trace names the kernel's event
DW = ('%psg_depthwise_grad_w.3 = (f32[9,8,144]{2,1,0:T(8,128)}, '
      'f32[9,8,144]{2,1,0:T(8,128)}) custom-call(s8[9,131072,144]{2,1,0:'
      'T(8,128)(4,1)} %a, s16[131072,144]{1,0:T(8,128)(2,1)} %b, s8[9,'
      '131072,144]{2,1,0:T(8,128)(4,1)} %c, s16[131072,144]{1,0:T(8,128)'
      '(2,1)} %d), custom_call_target="tpu_custom_call"')
MATMUL = ('%custom-call.7 = f32[256,144]{1,0:T(8,128)} custom-call(s8[131072,'
          '256]{1,0:T(8,128)(4,1)} %a, s16[131072,144]{1,0:T(8,128)(2,1)} '
          '%b), custom_call_target="tpu_custom_call"')
FUSION = '%fusion.3 = f32[128,32,32,16]{0,3,2,1} fusion(f32[16] %p), kind=kLoop'
PSG = {"enabled": True, "bits_x": 8, "bits_g": 16, "bits_x_msb": 4,
       "bits_g_msb": 10}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ARCH = {"kind": "mobilenetv2", "classes": 10, "image_hw": 32, "channels": 3}


def _trace():
    # busy 1000-2000 (matmul kernel), 2000-6000 (depthwise), 6000-7000
    return T.reduce([tpu(0, [Ev(MATMUL, 1000, 1000), Ev(DW, 2000, 4000),
                             Ev(FUSION, 6000, 1000)]), host()],
                    gap_min_ns=100)


def _record(**kw):
    return dict({"arch": ARCH, "batch": 128, "chips": 1, "psg": PSG,
                 "peak": PEAK}, **kw)


def test_the_depthwise_kernel_is_told_apart_by_its_rank_3_taps(bench):
    from bench.metrics_common import operand_shapes
    from bench.metrics_dw import dw_kernel_ops, match, sites
    tr = _trace()
    assert [op.text for op in dw_kernel_ops(tr.devices[0])] == [DW]
    dws = sites(_record())
    site = match(operand_shapes(DW), dws)
    assert (site["in_hw"], site["out_hw"], site["C"], site["M"]) == (
        32, 32, 144, 131072)
    assert match(operand_shapes(MATMUL), dws) is None
    assert sites(_record(arch={"kind": "resnet"})) is None
    share = bench.reader("dw_kernel_share")(_record(), tr)
    assert share == pytest.approx(100 * 4000 / 6000)


def test_the_depthwise_roofline_counts_each_activation_once(bench):
    m, c, b = 131072, 144, 128
    nbytes = (b * 32 * 32 * c * 12 + m * c * 26) / 8 + 2 * 9 * c * 4
    least = max(4.0 * 9 * m * c / 197e12, nbytes / 819e9)
    got = bench.reader("dw_kernel_roofline")(_record(), _trace())
    assert got == pytest.approx(100 * least / 4000e-9)
    assert bench.reader("dw_kernel_roofline")(
        _record(arch={"kind": "resnet"}), _trace()) is None


def test_the_depthwise_readers_read_nothing_where_there_is_nothing(bench):
    empty = T.reduce([tpu(0, [Ev(MATMUL, 1000, 1000)]), host()],
                     gap_min_ns=100)
    for name in ("dw_kernel_share", "dw_kernel_roofline"):
        assert bench.reader(name)(_record(), empty) is None
        assert bench.reader(name)(_record(psg={"enabled": False}),
                                  _trace()) is None
    read = bench.reader("dw_predicted_share")
    assert read({"history": [{"total_loss": 1.0}]}, None) is None
    hist = [{"psg_dw_fallback_ratio": 0.25}, {"psg_dw_fallback_ratio": 0.75}]
    assert read({"history": hist}, None) == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# correct on a tiny MobileNetV2 cell, under the real cell's limits
# ---------------------------------------------------------------------------

TINY = "tiny.mbv2"


def cell_limits():
    path = REPO / "bench" / "limits" / f"{CELL}.json"
    return json.loads(path.read_text())["limits"]


def make_mbv2_root(tmp: Path) -> Path:
    """The tiny benchmark root with a MobileNetV2 cell: widths unchanged,
    8x8 images, batch 4, chunks of 2 executed steps, the e2train mix on
    the conv path the chip runs (``fused_conv`` off)."""
    root = bench_tiny.make_root(tmp, limits={})
    cfg = json.loads((REPO / "bench/configs/mobilenetv2.json").read_text())
    cfg["arch"]["image_hw"] = 8
    cfg["reduced"] = ["image_hw"]
    (root / "bench/configs/tiny_mbv2.json").write_text(json.dumps(cfg))
    mix = bench_tiny.tiny_mix("e2train", batch=4, chunk=2)
    mix["e2train"]["psg"]["fused_conv"] = False
    (root / "bench/mixes/tiny_mbv2.json").write_text(json.dumps(mix))
    (root / "bench/limits" / f"{TINY}.json").write_text(json.dumps(
        {"limits": cell_limits(), "readings": {}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_mbv2", "source": cfg["source"],
                            "file": "bench/configs/tiny_mbv2.json",
                            "reduced": cfg["reduced"], "why": "CPU tests"})
    spec["workloads"].append({"name": TINY, "config": "tiny_mbv2",
                              "traffic": "tiny_mbv2", "chips": 1,
                              "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """The numbers of ``bench/correct.py`` for the program's first chunk,
    and, as ``bench/calibrate.py`` makes them, for its state returned
    unchanged, for the reference on half of each batch and for the
    reference in bfloat16 (the control) in its place; one program compile
    in one process."""
    import jax
    import jax.numpy as jnp
    from bench import run as R
    root = make_mbv2_root(tmp_path_factory.mktemp("bench"))
    b = S.Benchmark(root)
    with jax.default_matmul_precision(R.MATMUL_PRECISION):
        cr = R.CellRun(b, b.cell(TINY), 7, R.devices_for(1, "cpu"))
        cr.build()
        cr.first_chunk()
        cr.release()
        numbers, prog, ref = cr.check()
        start = cr.start
        unchanged = dict(prog, params_after=start.params,
                         buffer=cr.fam.optimizer_buffer(start.opt),
                         state=start.model_state)
        half = cr.check(fault=calibrate.half)[0]
        control = cr.check(control_dtype=jnp.bfloat16)[0]
    return {"program": numbers, "unchanged": C.compare(unchanged, ref),
            "half_batch": half, "control": control}


@pytest.mark.parametrize("kind,correct", [
    ("program", True), ("unchanged", False), ("half_batch", False),
    ("control", False)])
def test_the_cells_limits_pass_the_program_and_catch_the_faults(
        readings, kind, correct):
    ok, checks = C.verdict(readings[kind], cell_limits())
    assert ok == correct, checks


def test_the_program_agrees_with_the_reference_on_the_chips_conv_path(
        readings):
    """On the im2col path the program and the reference compute the same
    f32 operations: every number of ``bench/correct.py`` reads zero up to
    f32 rounding, and the SLU decisions and the SMD steps agree."""
    row = readings["program"]
    for k in ("loss_first", "loss_rest", "gate_first", "grad_norm_gap",
              "update_norm_gap", "state_norm_gap"):
        assert row[k] <= 1e-5, (k, row[k])
    for k in ("smd_steps", "slu_executed", "step_counter"):
        assert row[k] == 0, (k, row[k])
