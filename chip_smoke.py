"""Smoke run of the E2-Train main path on a TPU.

    PYTHONPATH=src python chip_smoke.py               # one chip
    PYTHONPATH=src python chip_smoke.py --four-chips  # data parallel, 4 chips

One process drives the chip.  With no option it runs three phases:

1. E2-Train: ResNet-74 on CIFAR-10-shaped data, batch 128, width 16, with
   SMD (drop probability 0.5), SLU and PSG (``psg`` optimizer), through
   ``Trainer``'s chunked loop, with the settings of
   ``examples/train_e2e.py``.  The trainer's own chunk program is compiled
   first, to time the compile and to check that the PSG kernels in it were
   lowered through Mosaic (``tpu_custom_call``), not interpreted.
2. Baseline: the same model and batch with E2-Train off (the paper's SGD
   configuration, ``configs/paper_cnns.resnet74``).
3. Kernel agreement: the Mosaic PSG weight gradient at the stage-1 body
   shape (N=131072, din=144, dout=16) against the element-level oracle
   ``kernels/ref.psg_grad_w_ref`` computed on the host CPU; signs must be
   identical.

``--four-chips`` runs only the data-parallel phase: ResNet-74 at global
batch 128 on a (4, 1) ("data", "model") mesh against one device, at f32
matmul precision.  With E2-Train off the two trainers step in lockstep
from the same state: each step's loss must agree to fp tolerance, and its
parameter update to within a few times what reversing the batch's rows
changes it on one chip.  With E2-Train on they run free: SMD counters
identical, losses finite; and the sharded PSG weight gradient at the
agreement shape matches the host-CPU oracle except where a sign is decided
within f32 summation error.

Without a TPU the script exits nonzero before any phase.  Any failed
check raises.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.configs.paper_cnns import resnet74  # noqa: E402
from repro.core.config import (E2TrainConfig, PSGConfig, SLUConfig,  # noqa: E402
                               SMDConfig, TrainConfig)

SEED = 0
BATCH = 128
CHUNK = 4                       # executed steps per compiled chunk
MIN_EXECUTED = 8
# stage-1 body conv of ResNet-74 at batch 128: B*32*32 rows, 3*3*16 -> 16
AGREE_SHAPE = (131072, 144, 16)
# one step of a data-parallel run against one device from the same state,
# at f32 matmul precision: only the f32 summation order differs
LOCKSTEP_RTOL = 1e-4
# the 4-chip update may differ from the 1-chip one by this many times what
# reversing the batch's rows on one chip changes it (lockstep)
ORDER_FACTOR = 4.0
# an f32 sum of N terms drifts by about sqrt(N) * 2^-24 * ||terms||_2 with
# the summation order; a PSG sign closer than this many drifts to a
# decision boundary may flip between two orders
SIGN_BAND = 16.0


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def require_tpu(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"jax {jax.__version__}; platform {d.platform}; "
          f"device_kind {d.device_kind}; device count {len(devs)}",
          flush=True)
    check(d.platform == "tpu", f"no TPU (platform {d.platform!r})")
    check(len(devs) >= chips, f"{chips} chips needed, {len(devs)} visible")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def e2_experiment(total_steps: int):
    """ResNet-74 with E2-Train on; train settings of examples/train_e2e.py."""
    e2 = E2TrainConfig(smd=SMDConfig(enabled=True, drop_prob=0.5),
                       slu=SLUConfig(enabled=True, alpha=1e-3),
                       psg=PSGConfig(enabled=True))
    return resnet74(e2=e2).replace(train=TrainConfig(
        global_batch=BATCH, lr=0.03, optimizer="psg",
        total_steps=total_steps, schedule="step", microbatches=1))


def baseline_experiment():
    exp = resnet74()
    check(exp.train.global_batch == BATCH, "resnet74 batch is not 128")
    return exp


def nominal_steps(exp, executed: int) -> int:
    """Nominal steps whose SMD schedule keeps exactly ``executed`` steps
    (the last one kept, so every chunk is full)."""
    from repro.core.smd import smd_keep_host
    if not exp.e2.smd.enabled:
        return executed
    n = kept = 0
    while kept < executed:
        kept += bool(smd_keep_host(exp.train.seed, n, exp.e2.smd.drop_prob))
        n += 1
    return n


def batch_maker(reverse: bool = False):
    """Seeded CIFAR-shaped batches; ``reverse`` serves each batch's rows in
    reverse order: the same examples, so the same step summed in another
    order."""
    from repro.data.synthetic import GaussianImageTask, make_image_batch
    task = GaussianImageTask(num_classes=10, snr=2.0)

    def make(step, shard):
        b = make_image_batch(task, SEED, step, shard, BATCH)
        return {k: v[::-1] for k, v in b.items()} if reverse else b
    return make


def chunk_seconds(hist) -> list:
    """Wall seconds of each chunk; the trainer spreads a chunk's time
    evenly over its executed steps."""
    walls = [h["wall_s"] for h in hist]
    return [sum(walls[i:i + CHUNK]) for i in range(0, len(walls), CHUNK)]


def check_losses(hist, label: str) -> list:
    losses = [h["total_loss"] for h in hist]
    check(bool(losses) and all(np.isfinite(losses)),
          f"{label}: non-finite loss in {losses}")
    return losses


def make_trainer(exp, chunk_steps: int = CHUNK, mesh=None,
                 reverse: bool = False):
    import jax
    from repro.training.train_step import init_train_state
    from repro.training.trainer import Trainer
    state = init_train_state(jax.random.PRNGKey(SEED), exp)
    return Trainer(exp, state, batch_maker(reverse), chunk_steps=chunk_steps,
                   deadline_s=0.0, mesh=mesh)


def run_trainer(tr, executed: int, label: str):
    import jax
    n = nominal_steps(tr.exp, executed)
    t0 = time.perf_counter()
    hist = tr.run(n)
    jax.block_until_ready(tr.state)
    wall = time.perf_counter() - t0
    losses = check_losses(hist, label)
    print(f"{label}: {n} nominal steps, executed {tr.executed_steps}, "
          f"SMD-dropped {tr.dropped_steps}, run {wall:.3f} s, chunk s "
          f"{[round(s, 4) for s in chunk_seconds(hist)]}", flush=True)
    print(f"{label}: losses {[round(v, 5) for v in losses]}", flush=True)
    return hist


def first_chunk(tr):
    """The first chunk's batches and step increments, placed as the
    trainer places them."""
    from repro.training.loop import stack_batches
    mk = batch_maker()
    return tr.place(stack_batches([mk(s, 0) for s in range(CHUNK)]),
                    np.ones(CHUNK, np.int32))


def compile_chunk(tr) -> float:
    """Compile the trainer's own chunk program on its placed arguments;
    return the compile seconds after checking that the PSG kernels in it
    are Mosaic custom calls."""
    batches, incs = first_chunk(tr)
    t0 = time.perf_counter()
    compiled = tr.chunk_program().lower(tr.state, batches, incs).compile()
    secs = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    print(f"e2train: chunk program (K={CHUNK}) compiled in {secs:.3f} s; "
          f"{n_kernels} tpu_custom_call sites", flush=True)
    check(n_kernels > 0, "no Mosaic kernel in the compiled train step")
    return secs


def e2train_phase() -> None:
    from repro.kernels import dispatch
    backend = dispatch.default_backend()
    print(f"kernel backend: {backend}", flush=True)
    check(backend == dispatch.BACKEND_MOSAIC,
          f"kernel backend {backend!r}, expected mosaic")
    exp = e2_experiment(nominal_steps(e2_experiment(1), 3 * CHUNK))
    check(exp.e2.psg.backend == "auto", "PSG backend pinned away from auto")
    tr = make_trainer(exp)
    compile_chunk(tr)
    hist = run_trainer(tr, 3 * CHUNK, "e2train")
    check(tr.executed_steps >= MIN_EXECUTED,
          f"only {tr.executed_steps} steps executed")
    check(tr.dropped_steps > 0, "SMD dropped no step")
    ratios = [h["psg_fallback_ratio"] for h in hist]
    check(all(0.0 <= r <= 1.0 for r in ratios),
          f"psg_fallback_ratio outside [0, 1]: {ratios}")
    print(f"e2train: psg_fallback_ratio {[round(r, 4) for r in ratios]}",
          flush=True)


def baseline_phase() -> None:
    run_trainer(make_trainer(baseline_experiment()), 2 * CHUNK, "baseline")


def agreement_data():
    """Seeded PSG operands at AGREE_SHAPE: post-ReLU activations (exact
    zeros included) against dense gradients."""
    n, din, dout = AGREE_SHAPE
    rng = np.random.default_rng(SEED)
    x = np.maximum(rng.standard_normal((n, din), np.float32), 0.0)
    gy = rng.standard_normal((n, dout), np.float32)
    return x, gy


def oracle_signs(x, gy, cfg) -> np.ndarray:
    """The element-level oracle on the host CPU: the chip's default f32
    matmul precision would make an on-chip reference inexact."""
    import jax
    from repro.kernels import ref
    cpu = jax.devices("cpu")[0]
    return np.asarray(jax.jit(lambda a, b: ref.psg_grad_w_ref(a, b, cfg))(
        jax.device_put(x, cpu), jax.device_put(gy, cpu)))


def kernel_agreement_phase() -> None:
    import jax
    from repro.kernels import dispatch
    x, gy = agreement_data()
    cfg = PSGConfig(enabled=True)
    sign, ratio = jax.jit(lambda a, b: dispatch.psg_grad_w(a, b, cfg))(x, gy)
    sign = np.asarray(sign)
    differ = int(np.sum(sign != oracle_signs(x, gy, cfg)))
    print(f"kernel agreement {AGREE_SHAPE}: {differ} of {sign.size} signs "
          f"differ from the host-CPU oracle; tile fallback ratio "
          f"{float(ratio):.4f}", flush=True)
    check(differ == 0, "Mosaic PSG signs differ from the oracle")
    check(0.0 <= float(ratio) <= 1.0, "fallback ratio outside [0, 1]")


def flip_band(x, gy, cfg) -> np.ndarray:
    """Entries whose PSG sign the f32 summation order may decide: the full
    code product, or the predictor's margin over tau, lies within
    SIGN_BAND summation drifts of zero.  Exact products in float64."""
    import jax
    from repro.core.quant import quantize_int
    cpu = jax.devices("cpu")[0]
    xm, gm, xq, gq = (
        np.asarray(quantize_int(jax.device_put(a, cpu), bits)[0], np.float64)
        for a, bits in ((x, cfg.bits_x_msb), (gy, cfg.bits_g_msb),
                        (x, cfg.bits_x), (gy, cfg.bits_g)))

    def drift(a, b):
        return (SIGN_BAND * np.sqrt(a.shape[0]) * 2.0 ** -24
                * np.sqrt((a * a).T @ (b * b)))

    g_msb, d_msb = xm.T @ gm, drift(xm, gm)
    tau = cfg.beta * np.max(np.abs(g_msb))
    near_tau = np.abs(np.abs(g_msb) - tau) <= d_msb + cfg.beta * d_msb.max()
    return near_tau | (np.abs(xq.T @ gq) <= drift(xq, gq))


def mesh_kernel_agreement(mesh) -> None:
    """The PSG weight gradient with its rows split over the mesh (per-shard
    Mosaic products, summed) against the host-CPU oracle."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import activation_sharding
    from repro.kernels import dispatch
    x, gy = agreement_data()
    cfg = PSGConfig(enabled=True)
    rows = NamedSharding(mesh, P("data", None))
    xs, gs = jax.device_put(x, rows), jax.device_put(gy, rows)
    with activation_sharding(mesh), mesh:
        compiled = jax.jit(lambda a, b: dispatch.psg_grad_w(a, b, cfg)
                           ).lower(xs, gs).compile()
    text = compiled.as_text()
    check("tpu_custom_call" in text and "all-reduce" in text,
          "sharded PSG weight gradient is not per-shard Mosaic + all-reduce")
    sign, ratio = compiled(xs, gs)
    differ = np.asarray(sign) != oracle_signs(x, gy, cfg)
    band = flip_band(x, gy, cfg)
    print(f"mesh kernel agreement {AGREE_SHAPE}: {int(differ.sum())} of "
          f"{differ.size} signs differ from the host-CPU oracle, "
          f"{int((differ & ~band).sum())} outside the f32 summation band "
          f"({int(band.sum())} entries in it); tile fallback ratio "
          f"{float(ratio):.4f}", flush=True)
    check(not np.any(differ & ~band),
          "sharded PSG signs differ from the oracle outside the f32 band")


def check_spans(tr, mesh, label: str) -> None:
    """The batch and the train state span every device of the mesh."""
    import jax
    devices = set(mesh.devices.flat)
    batches, _ = first_chunk(tr)
    for name, arr in batches.items():
        rows = {s.data.shape[1] for s in arr.addressable_shards}
        check(arr.sharding.device_set == devices and rows == {BATCH // 4},
              f"{label}: batch {name!r} not split over 4 devices")
    leaves = jax.tree.leaves(tr.state)
    check(all(a.sharding.device_set == devices for a in leaves),
          f"{label}: train state not on all 4 devices")
    print(f"{label}: batch split {BATCH // 4} rows per device on "
          f"{len(devices)} devices; {len(leaves)} state arrays span all "
          f"{len(devices)}", flush=True)


def rel_gap(a, b) -> float:
    return float(np.abs(a - b) / np.abs(a))


def lockstep(exp, mesh, executed: int, label: str):
    """Step three trainers one step at a time from the same state: one
    chip, one chip fed each batch in reverse row order (the control), and
    four chips; return the 4-chip trainer.  Each step's loss must agree to
    LOCKSTEP_RTOL.  The parameter update is a sum over the batch that
    largely cancels before BatchNorm, so its f32 summation error is far
    above the loss's: the 4-chip update must lie within ORDER_FACTOR times
    the control's distance from the 1-chip update."""
    import jax
    from repro.distributed.sharding import state_shardings

    def flat(tree):
        return np.concatenate([np.ravel(v) for v in jax.tree.leaves(tree)])

    one = make_trainer(exp, chunk_steps=1)
    rev = make_trainer(exp, chunk_steps=1, reverse=True)
    four = make_trainer(exp, chunk_steps=1, mesh=mesh)
    gaps = []
    while one.executed_steps < executed:
        before = jax.device_get(one.state)
        rev.state = jax.device_put(before)
        four.state = jax.device_put(before, state_shardings(before, mesh))
        for tr in (one, rev, four):
            tr.run(1)
        check(len({(tr.executed_steps, tr.dropped_steps)
                   for tr in (one, rev, four)}) == 1,
              f"{label}: SMD counters differ")
        if len(one.history) == len(gaps):
            continue                            # an SMD-dropped step
        p0 = flat(before.params)
        u1, ur, u4 = (flat(jax.device_get(tr.state.params)) - p0
                      for tr in (one, rev, four))
        gaps.append((rel_gap(one.history[-1]["total_loss"],
                             four.history[-1]["total_loss"]),
                     float(np.linalg.norm(u4 - u1) / np.linalg.norm(u1)),
                     float(np.linalg.norm(ur - u1) / np.linalg.norm(u1))))
    for tr, where in ((one, "1 chip"), (rev, "1 chip reversed"),
                      (four, "4 chips")):
        check_losses(tr.history, f"{label} {where}")
    print(f"{label}: losses 1 chip "
          f"{[round(h['total_loss'], 5) for h in one.history]}", flush=True)
    for name, col in (("loss gap 4 chips vs 1", 0),
                      ("update gap 4 chips vs 1", 1),
                      ("update gap reversed rows vs 1 chip", 2)):
        print(f"{label}: lockstep relative {name} per step "
              f"{[float(f'{g[col]:.3e}') for g in gaps]}", flush=True)
    check(all(loss <= LOCKSTEP_RTOL for loss, _, _ in gaps),
          f"{label}: a lockstep loss differs by more than {LOCKSTEP_RTOL}")
    check(all(u4 <= ORDER_FACTOR * ur for _, u4, ur in gaps),
          f"{label}: a 4-chip update differs by more than {ORDER_FACTOR}x "
          f"the reversed-row control")
    return four


def four_chip_phase() -> None:
    """Data parallel on 4 chips against 1 chip.

    E2-Train off steps in lockstep at f32 matmul precision, so a gap
    measures the sharding of one step and not the bf16 rounding of two
    differently compiled programs; the reversed-row control measures what
    the f32 summation order alone does to that step, on the same chip
    kind and at the same precision.  Two free runs are not compared: from a
    random init the loss of ResNet-74 spikes over the first steps, and
    that amplifies f32 summation-order differences about tenfold per step
    (PERF.md).  The E2-Train-off run uses the E2-Train run's settings
    (lr 0.03).  E2-Train on runs free, at the default precision: PSG sign
    votes may flip with the cross-device summation order."""
    import jax
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 1), ("data", "model"))
    e2 = e2_experiment(nominal_steps(e2_experiment(1), 2 * CHUNK))
    off = e2.replace(e2=E2TrainConfig(), train=dataclasses.replace(
        e2.train, optimizer="sgdm"))
    with jax.default_matmul_precision("highest"):
        four = lockstep(off, mesh, 2 * CHUNK, "e2train off")
    check_spans(four, mesh, "e2train off")
    one, four = make_trainer(e2), make_trainer(e2, mesh=mesh)
    h1 = run_trainer(one, 2 * CHUNK, "e2train 1 chip")
    h4 = run_trainer(four, 2 * CHUNK, "e2train 4 chips")
    check_spans(four, mesh, "e2train")
    check([h["step"] for h in h1] == [h["step"] for h in h4],
          "e2train: executed steps differ")
    check((one.executed_steps, one.dropped_steps)
          == (four.executed_steps, four.dropped_steps),
          "e2train: SMD counters differ")
    gaps = [rel_gap(a["total_loss"], b["total_loss"]) for a, b in zip(h1, h4)]
    print(f"e2train: relative loss gap 4 chips vs 1 per step "
          f"{[float(f'{g:.3e}') for g in gaps]}", flush=True)
    mesh_kernel_agreement(mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel phase")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import use_compile_cache
    print(f"compilation cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        e2train_phase()
        baseline_phase()
        kernel_agreement_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
