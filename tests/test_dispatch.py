"""Kernel dispatch layer: backend selection + PSG backward-through-kernel.

These tests pin the PR-1 acceptance criteria: the training backward runs
the tile-level Pallas kernel (not the element-level oracle), its signs are
bit-identical to ``psg_grad_w_ref`` on the shape sweep, and the measured
fallback-tile ratio reaches the train-step metrics dict.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import psg
from repro.core.config import (E2TrainConfig, Experiment, ModelConfig,
                               PSGConfig, TrainConfig)
from repro.kernels import dispatch, ref

CFG = PSGConfig(enabled=True)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------


def test_default_backend_platform_probe():
    want = "mosaic" if jax.default_backend() == "tpu" else "interpret"
    assert dispatch.default_backend() == want
    assert dispatch.resolve_backend(CFG) == want          # cfg "auto" defers


def test_config_pins_backend():
    pinned = PSGConfig(enabled=True, backend="reference")
    assert dispatch.resolve_backend(pinned) == "reference"


def test_override_wins_over_config():
    pinned = PSGConfig(enabled=True, backend="reference")
    with dispatch.override_backend("interpret"):
        assert dispatch.resolve_backend(pinned) == "interpret"
    assert dispatch.resolve_backend(pinned) == "reference"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        dispatch.resolve_backend(PSGConfig(enabled=True, backend="cuda"))
    with pytest.raises(ValueError):
        dispatch.set_default_backend("nope")


def test_no_env_reads_in_traced_code():
    """Trace the dispatched op and the PSG custom_vjp under a monkeypatched
    environ that explodes on access: selection must be trace-time pure."""
    import os
    real_get = os.environ.get

    def boom(*a, **k):
        raise AssertionError("os.environ read inside traced code")

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    os.environ.get = boom
    try:
        jax.jit(lambda a, b: psg.psg_matmul(a, b, CFG)).lower(x, w)
        jax.jit(jax.grad(lambda b: jnp.sum(psg.psg_matmul(x, b, CFG)))
                ).lower(w)
    finally:
        os.environ.get = real_get


# ---------------------------------------------------------------------------
# backward pass runs the tile kernel, bit-identical to the oracle
# ---------------------------------------------------------------------------

SHAPES = [(64, 32, 48), (300, 130, 70), (512, 256, 128), (1024, 256, 256),
          (128, 7, 9)]


@pytest.mark.parametrize("N,din,dout", SHAPES)
def test_psg_bwd_signs_bit_identical_to_ref(N, din, dout):
    k1, k2 = jax.random.split(jax.random.PRNGKey(N + din))
    x = jax.random.normal(k1, (N, din)) * 0.5
    gy = jax.random.normal(k2, (N, dout)) * 0.01
    w = jax.random.normal(jax.random.PRNGKey(0), (din, dout)) * 0.1

    # sum(y * gy) makes gy the exact cotangent reaching _psg_bwd
    dw = jax.grad(lambda b: jnp.sum(psg.psg_matmul(x, b, CFG) * gy))(w)
    want = ref.psg_grad_w_ref(x, gy, CFG)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(want))
    assert set(np.unique(np.asarray(dw))).issubset({-1.0, 0.0, 1.0})


def test_bwd_executes_tile_kernel_not_oracle():
    """The traced backward must contain the Pallas kernel's tile-stats
    output — an artifact the element-level oracle does not produce."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda b: jnp.sum(psg.psg_matmul(x, b, CFG))))(w)
    assert "pallas_call" in str(jaxpr)
    with dispatch.override_backend("reference"):
        jaxpr_ref = jax.make_jaxpr(
            jax.grad(lambda b: jnp.sum(psg.psg_matmul(x, b, CFG))))(w)
    assert "pallas_call" not in str(jaxpr_ref)


def test_reference_backend_matches_tile_backend():
    x = jax.random.normal(jax.random.PRNGKey(3), (512, 96)) * 0.5
    gy = jax.random.normal(jax.random.PRNGKey(4), (512, 40)) * 0.01
    with dispatch.override_backend("interpret"):
        s_tile, fb_tile = dispatch.psg_grad_w(x, gy, CFG)
    with dispatch.override_backend("reference"):
        s_ref, fb_ref = dispatch.psg_grad_w(x, gy, CFG)
    np.testing.assert_array_equal(np.asarray(s_tile), np.asarray(s_ref))
    assert 0.0 <= float(fb_tile) <= 1.0
    assert 0.0 <= float(fb_ref) <= 1.0


# ---------------------------------------------------------------------------
# fallback stats reach the training metrics
# ---------------------------------------------------------------------------


def test_probe_accumulates_across_matmuls():
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    w1 = jax.random.normal(jax.random.PRNGKey(6), (32, 32)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(7), (32, 16)) * 0.1

    def loss(ws, probe):
        with psg.enable(CFG, probe=probe):
            h = psg.matmul(x, ws[0])
            return jnp.sum(psg.matmul(h, ws[1]))

    pg = jax.grad(loss, argnums=1)((w1, w2), psg.zero_probe())
    # MAC-weighted accumulation: both matmuls' MAC counts summed
    macs = 64 * 32 * 32 + 64 * 32 * 16
    assert float(pg[1]) == float(macs)
    assert 0.0 <= float(pg[0]) <= float(macs)
    ratio = psg.probe_fallback_ratio(pg)
    assert 0.0 <= float(ratio) <= 1.0


def test_train_step_reports_measured_fallback_ratio():
    from repro.training.train_step import init_train_state, make_train_step
    model = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=32,
                        dtype="float32")
    exp = Experiment(model=model,
                     e2=E2TrainConfig(psg=PSGConfig(enabled=True, swa=False)),
                     train=TrainConfig(global_batch=4, seq_len=8, lr=0.03,
                                       optimizer="psg", total_steps=4,
                                       schedule="constant"))
    state = init_train_state(jax.random.PRNGKey(0), exp)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 8), 0, 32),
             "labels": jax.random.randint(key, (4, 8), 0, 32)}
    _, metrics = jax.jit(make_train_step(exp))(state, batch)
    fb = float(metrics["psg_fallback_ratio"])
    assert 0.0 < fb <= 1.0, fb

    # PSG off: no measurement taken, so the metric must be absent (a
    # baseline step has no data, not a measurement of zero)
    exp_off = Experiment(model=model, train=exp.train)
    st2 = init_train_state(jax.random.PRNGKey(0), exp_off)
    _, m2 = jax.jit(make_train_step(exp_off))(st2, batch)
    assert "psg_fallback_ratio" not in m2


def test_energy_uses_measured_fallback():
    from repro.core import energy
    model = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=64)
    e2 = E2TrainConfig(psg=PSGConfig(enabled=True))
    lo = energy.training_energy_pj(model, 4, 32, e2, 10, psg_fallback_rate=0.1)
    hi = energy.training_energy_pj(model, 4, 32, e2, 10, psg_fallback_rate=0.9)
    assert lo < hi                        # more fallback -> more energy
    f_lo = energy.measured_psg_factor(e2, 0.1)
    f_hi = energy.measured_psg_factor(e2, 0.9)
    assert f_lo < f_hi < 1.0


def test_data_parallel_psg_grad_w_matches_one_device():
    """Under a data-parallel mesh the kernel runs per shard inside
    shard_map (a Mosaic kernel cannot be partitioned by the compiler) and
    the partial code products are summed: same signs and the same tile
    fallback ratio as the one-device kernel.  Subprocess: the suite keeps
    the default single-device runtime."""
    import os
    import subprocess
    import sys
    script = r"""
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 4
from repro.core.config import PSGConfig
from repro.distributed.sharding import activation_sharding
from repro.kernels import dispatch, ops
from repro.launch.mesh import make_mesh
cfg = PSGConfig(enabled=True)
rng = np.random.default_rng(0)
for n, din, dout in ((256, 144, 16), (64, 200, 130)):
    x = jnp.asarray(np.maximum(rng.standard_normal((n, din)), 0), jnp.float32)
    g = jnp.asarray(rng.standard_normal((n, dout)), jnp.float32)
    want, want_r = ops.psg_grad_w(x, g, cfg)
    mesh = make_mesh((4, 1), ("data", "model"))
    with activation_sharding(mesh):
        got, got_r = jax.jit(lambda a, b: dispatch.psg_grad_w(a, b, cfg))(x, g)
        jaxpr = jax.make_jaxpr(lambda a, b: dispatch.psg_grad_w(a, b, cfg))(x, g)
    assert "shard_map" in str(jaxpr)
    assert np.array_equal(np.asarray(got), np.asarray(want)), (n, din, dout)
    assert float(got_r) == float(want_r), (float(got_r), float(want_r))
print("SHARDED_OK")
"""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
