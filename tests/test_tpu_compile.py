"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler runs here against a described
``v5e:2x2`` topology and refuses what Mosaic would refuse on the chip
(block tiling, scalar stores to VMEM, unsupported vector ops).  Interpret
mode accepts all of those, so these compiles are the authority on whether
a kernel lowers; ``analysis/kernel_lint.py`` is the fast pre-check.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The kernels on the paper's main path are compiled at the real
ResNet-74 batch-128 shapes; ``tpu_custom_call`` in the compiled text shows
the kernel was lowered through Mosaic and not interpreted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.paper_cnns import resnet_conv_shapes, resnet_im2col_shapes
from repro.core.config import PSGConfig
from repro.distributed.sharding import activation_sharding
from repro.kernels import conv, dispatch, flash_attn, ops, psg_matmul, quant

CFG = PSGConfig(enabled=True)
# every distinct im2col matmul of ResNet-74 at batch 128, plus the fc head
PSG_SHAPES = resnet_im2col_shapes(74, 16, 128) + [(128, 64, 10)]
# conv kinds whose fused kernels compile; the stride-2 tap gather of the
# "strided" kind is refused by Mosaic (strided vector slices), so only its
# input-gradient kernel (stride-1 phase windows) is compiled
_CONVS = {c.kind: c for c in resnet_conv_shapes(74, 16, 128)}
FUSED_KINDS = ("body", "down")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the cache but cannot be
    # read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _conv_geometry(kind):
    """(k, stride, batch, padded hw, out hw, cin, cout) of the deepest
    ResNet-74 conv of ``kind``, normalized as ``core/psg.conv2d`` does."""
    c = _CONVS[kind]
    k, s, hw = c.k, c.stride, c.hw
    if k < s:                       # 1x1 stride-2: pre-subsampled stride-1
        hw, s = -(-hw // s), 1
    hp = hw + 2 * (k // 2)
    return k, s, c.batch, hp, (hp - k) // s + 1, c.cin, c.cout


@pytest.mark.parametrize("n, din, dout", PSG_SHAPES)
def test_psg_grad_w_compiles(one_chip, n, din, dout):
    f32 = jnp.float32
    _compile(lambda x, g: ops.psg_grad_w(x, g, CFG, interpret=False),
             one_chip, ((n, din), f32), ((n, dout), f32))


def test_predictor_matmul_compiles(one_chip):
    # 4-bit x codes in int8, 10-bit g codes in int16 (ops._codes)
    n, din, dout = resnet_im2col_shapes(74, 16, 128)[1]
    _compile(lambda a, b: psg_matmul.predictor_matmul_pallas(
        a, b, interpret=False),
        one_chip, ((n, din), jnp.int8), ((n, dout), jnp.int16))


def test_quantize_compiles(one_chip):
    _compile(lambda x: quant.quantize_pallas(x, 8, interpret=False),
             one_chip, ((8192, 576), jnp.float32))


@pytest.mark.parametrize("kind", FUSED_KINDS)
def test_fused_conv_kernels_compile(one_chip, kind):
    k, s, b, hp, ho, cin, cout = _conv_geometry(kind)
    f32 = jnp.float32
    x, w, g = ((b, hp, hp, cin), f32), ((k * k * cin, cout), f32), \
        ((b, ho, ho, cout), f32)
    _compile(lambda a, c: ops.conv_fwd(a, c, k, s, interpret=False),
             one_chip, x, w)
    _compile(lambda a, c: ops.conv_grad_x(a, c, k, s, hp, hp,
                                          interpret=False), one_chip, g, w)
    _compile(lambda a, c: ops.conv_grad_w(a, c, CFG, k, s, interpret=False),
             one_chip, x, g)


# MobileNetV2's depthwise convs at batch 128: (input extent, channels,
# stride): C not a multiple of 128 in one channel tile, stride 2 on taps
# strided outside the kernel, and C=384 in three 128-lane tiles
DW_SHAPES = [(32, 144, 1), (8, 576, 2), (8, 384, 1)]


@pytest.mark.parametrize("hw, c, stride", DW_SHAPES)
def test_psg_depthwise_grad_w_compiles(one_chip, hw, c, stride):
    ho = -(-hw // stride)
    text = _compile(
        lambda x, g: ops.psg_depthwise_grad_w(x, g, CFG, stride,
                                              interpret=False),
        one_chip, ((128, hw, hw, c), jnp.float32),
        ((128, ho, ho, c), jnp.float32))
    assert "s8[9," in text or "psg_depthwise_grad_w" in text


def test_strided_conv_grad_x_compiles(one_chip):
    k, s, b, hp, ho, cin, cout = _conv_geometry("strided")
    _compile(lambda a, c: conv.conv_grad_x_pallas(a, c, k=k, stride=s, hp=hp,
                                                  wp=hp, interpret=False),
             one_chip, ((b, ho, ho, cout), jnp.float32),
             ((k * k * cin, cout), jnp.float32))


_Q = ((1, 2048, 8, 128), jnp.bfloat16)
_KV = ((1, 2048, 2, 128), jnp.bfloat16)


def test_flash_attention_fwd_compiles(one_chip):
    _compile(lambda a, b, c: flash_attn.flash_attention(
        a, b, c, causal=True, interpret=False, return_lse=True),
        one_chip, _Q, _KV, _KV)


def test_flash_attention_psg_bwd_compiles(one_chip):
    lse = ((1, 8, 2048), jnp.float32)
    text = _compile(
        lambda q, k, v, o, l, do: ops.flash_attention_bwd(
            q, k, v, o, l, do, CFG, causal=True, interpret=False),
        one_chip, _Q, _KV, _KV, _Q, lse, _Q)
    assert text.count("tpu_custom_call") >= 2      # dq and dk/dv kernels


def test_psg_grad_w_compiles_data_parallel_on_four_chips(topo, one_chip):
    """The compiler cannot partition a Mosaic kernel: on a data-parallel
    mesh the dispatch layer must run it per shard (shard_map)."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Explicit,) * 2)
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    n, din, dout = PSG_SHAPES[1]
    cfg = PSGConfig(enabled=True, backend="mosaic")
    args = [jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=rows)
            for d in (din, dout)]
    with activation_sharding(mesh):
        text = jax.jit(lambda x, g: dispatch.psg_grad_w(x, g, cfg)).lower(
            *args).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
