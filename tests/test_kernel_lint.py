"""Static Pallas kernel linter (analysis/kernel_lint.py): the shipped
registry is clean and deliberately broken kernels are caught."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import lint_shipped
from repro.analysis.kernel_lint import (VMEM_BUDGET_BYTES, LintFinding,
                                        lint_kernel)
from repro.kernels.dispatch import conv_lint_geometries, shipped_kernels


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _trace_call(out_block, out_index_map, grid=(2, 2)):
    """A 256x256 f32 copy through pallas_call with a configurable output
    BlockSpec — traced only (make_jaxpr), never executed."""
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def fn(a):
        return pl.pallas_call(
            _copy_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(out_block, out_index_map)],
            out_specs=pl.BlockSpec(out_block, out_index_map),
            out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            interpret=True,
        )(a)

    return fn, (x,)


# ---------------------------------------------------------------------------
# shipped kernels
# ---------------------------------------------------------------------------


def test_shipped_kernels_lint_clean():
    findings = lint_shipped()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_registry_enumerates_every_shipped_kernel():
    base = {n.split("[")[0] for n in shipped_kernels()}
    assert base == {
        "predictor_matmul_pallas", "conv_fwd_pallas",
        "conv_grad_w_predictor_pallas", "conv_grad_w_pallas",
        "conv_grad_x_pallas", "quantize_pallas", "flash_attention",
        "flash_bwd_dq_pallas", "flash_bwd_dkv_pallas"}


def test_conv_registry_covers_every_shipped_geometry_kind():
    """The conv entries are parameterized over the geometry kinds of
    ``configs/paper_cnns.resnet_conv_shapes`` (plus the MobileNetV2-style
    pointwise) — the old hardcoded ``partial(..., k=3)`` registry never
    linted the 1x1 conv geometries that actually ship."""
    geoms = conv_lint_geometries()
    assert set(geoms) == {"body", "strided", "down", "point"}
    ks = {kind: g[0] for kind, g in geoms.items()}
    assert ks["down"] == ks["point"] == 1 and ks["body"] == 3
    # the down kind arrives pre-subsample-normalized: never k < stride
    assert all(g[0] >= g[1] for g in geoms.values())
    names = set(shipped_kernels())
    for op in ("conv_fwd_pallas", "conv_grad_w_predictor_pallas",
               "conv_grad_w_pallas", "conv_grad_x_pallas"):
        for kind in geoms:
            assert f"{op}[{kind}]" in names, (op, kind)


def test_geometry_dependent_violation_is_caught():
    """A violation that exists only at a specific conv geometry must be
    caught when that geometry is linted: same kernel, same tile choice —
    clean where the block spans the full dout extent, a tile-alignment
    finding where it does not.  This is the failure mode the
    kind-parameterized registry exists to expose."""
    from repro.kernels import conv

    S = jax.ShapeDtypeStruct
    cx = S((4, 6, 6, 16), jnp.float32)
    fn = functools.partial(conv.conv_fwd_pallas, k=1, stride=1, bn=40,
                           interpret=True)
    # dout=40: the 40-wide block IS the full extent — clean
    assert lint_kernel(fn, cx, S((16, 40), jnp.float32), name="g40") == []
    # dout=120: identical call, different geometry — misaligned block
    rules = {f.rule for f in
             lint_kernel(fn, cx, S((16, 120), jnp.float32), name="g120")}
    assert "tile-alignment" in rules


def test_registry_grids_are_not_degenerate():
    """Every registered instantiation must tile (grid > 1 somewhere) —
    a coverage bug cannot hide behind a one-block grid."""
    for name, (fn, args) in shipped_kernels().items():
        closed = jax.make_jaxpr(fn)(*args)
        grids = [eqn.params["grid_mapping"].grid
                 for eqn in closed.jaxpr.eqns
                 if eqn.primitive.name == "pallas_call"]
        assert grids, name
        assert all(max(g) > 1 for g in grids), (name, grids)


# ---------------------------------------------------------------------------
# deliberately broken kernels
# ---------------------------------------------------------------------------


def test_uncovered_output_tile_is_caught():
    # constant index map: only block (0, 0) of the 2x2 lattice is written
    fn, args = _trace_call((128, 128), lambda i, j: (0, 0))
    rules = {f.rule for f in lint_kernel(fn, *args, name="bad")}
    assert "coverage" in rules


def test_oob_index_map_is_caught():
    fn, args = _trace_call((128, 128), lambda i, j: (i + 1, j))
    rules = {f.rule for f in lint_kernel(fn, *args, name="bad")}
    assert "oob-index" in rules


def test_mistiled_block_is_caught():
    # 100 is neither a multiple of 8 nor the full 256 extent
    fn, args = _trace_call((100, 256), lambda i, j: (i, 0), grid=(3, 1))
    findings = lint_kernel(fn, *args, name="bad")
    assert any(f.rule == "tile-alignment" for f in findings)


def test_well_tiled_copy_is_clean():
    fn, args = _trace_call((128, 128), lambda i, j: (i, j))
    assert lint_kernel(fn, *args, name="good") == []


def _flag_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


@pytest.mark.parametrize("flags, clean", [
    # one (1, 1) VMEM block per grid cell of a (2, 2) array: Mosaic refuses
    # it ("divisible by 8 and 128 ... or equal to ... the overall array"),
    # however small the block is
    (pl.BlockSpec((1, 1), lambda i, j: (i, j)), False),
    # the same flags as one whole-array SMEM operand compile
    (pl.BlockSpec(memory_space=pltpu.SMEM), True),
])
def test_subtile_vmem_block_is_caught_smem_is_not(flags, clean):
    def fn(a):
        return pl.pallas_call(
            _flag_kernel, grid=(2, 2), in_specs=[flags], out_specs=flags,
            out_shape=jax.ShapeDtypeStruct((2, 2), jnp.int32),
            interpret=True)(a)

    rules = {f.rule for f in lint_kernel(
        fn, jax.ShapeDtypeStruct((2, 2), jnp.int32), name="flags")}
    assert ("tile-alignment" not in rules) == clean, rules


def test_vmem_budget_overflow_is_caught():
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)

    def fn(a):
        return pl.pallas_call(
            _copy_kernel,
            grid=(1,),
            in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)),
            out_shape=big,
            interpret=True,
        )(a)

    findings = lint_kernel(fn, big, name="huge")
    (f,) = [f for f in findings if f.rule == "vmem-budget"]
    assert str(VMEM_BUDGET_BYTES // 2**20) in f.message


def test_ungated_accumulator_is_caught():
    """A reduction-axis kernel with scratch but no pl.when init/finish
    gating must produce both accumulator-discipline findings."""
    def kernel(x_ref, o_ref, acc_ref):
        acc_ref[...] += jnp.pad(x_ref[...], ((0, 0), (0, 128)))
        o_ref[...] = acc_ref[...]

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def fn(a):
        return pl.pallas_call(
            kernel,
            grid=(2, 2),                 # axis 1 reduces: out map ignores k
            in_specs=[pl.BlockSpec((128, 128), lambda i, k: (i, k))],
            out_specs=pl.BlockSpec((128, 256), lambda i, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            scratch_shapes=[pltpu.VMEM((128, 256), jnp.float32)],
            interpret=True,
        )(a)

    msgs = [f.message for f in lint_kernel(fn, x, name="bad")
            if f.rule == "accumulator-discipline"]
    assert len(msgs) == 2
    assert any("== 0" in m for m in msgs)
    assert any("== 1" in m for m in msgs)


def test_gated_accumulator_passes():
    def kernel(x_ref, o_ref, acc_ref):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.pad(x_ref[...], ((0, 0), (0, 128)))

        @pl.when(k == 1)
        def _finish():
            o_ref[...] = acc_ref[...]

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def fn(a):
        return pl.pallas_call(
            kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((128, 128), lambda i, k: (i, k))],
            out_specs=pl.BlockSpec((128, 256), lambda i, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            scratch_shapes=[pltpu.VMEM((128, 256), jnp.float32)],
            interpret=True,
        )(a)

    assert [f for f in lint_kernel(fn, x, name="good")
            if f.rule == "accumulator-discipline"] == []


def test_finding_formats_with_rule_and_kernel():
    f = LintFinding("k", "coverage", "m")
    assert str(f) == "[coverage] k: m"
