"""Paper Tab. 4: E²-Train on the paper's own backbones (ResNet family +
MobileNetV2) — the faithful-reproduction path, reduced depths for CPU.

Rows: baseline SMB vs E²-Train, on the class-conditional Gaussian image
task; savings come from ``Trainer.energy_report()`` — config-derived paper
composition next to the run's measured telemetry, priced by the per-layer
CNN cost model (core/cost.py).

Runs through the shared training stack (``repro.tasks`` "cifar_cnn" +
``Trainer``) — SMD drops, the PSG fallback probe, SLU metrics, and
eval-mode BatchNorm all come from the same code path the LM experiments
use; there is no CNN-specific training loop here.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.configs.paper_cnns import cnn_model
from repro.core.config import (E2TrainConfig, Experiment, PSGConfig,
                               SLUConfig, SMDConfig, TrainConfig)
from repro.data.synthetic import GaussianImageTask, make_image_batch
from repro.tasks import get_task
from repro.training.train_step import eval_params, init_train_state
from repro.training.trainer import Trainer

from benchmarks.common import csv_row, energy_fields

TASK = GaussianImageTask(num_classes=10, snr=2.0)
BATCH = 16


def _cnn_experiment(depth: int, e2: E2TrainConfig, steps: int, *,
                    optimizer="sgdm", lr=0.1) -> Experiment:
    return Experiment(
        model=cnn_model(f"resnet{depth}", depth),
        e2=e2,
        train=TrainConfig(global_batch=BATCH, lr=lr, optimizer=optimizer,
                          total_steps=steps, schedule="step",
                          weight_decay=5e-4),
        task="cifar_cnn")


def _train_resnet(depth: int, e2: E2TrainConfig, steps: int, *,
                  optimizer="sgdm", lr=0.1):
    exp = _cnn_experiment(depth, e2, steps, optimizer=optimizer, lr=lr)
    state = init_train_state(jax.random.PRNGKey(0), exp)
    trainer = Trainer(exp, state, lambda s, sh: make_image_batch(
        TASK, 0, s, sh, BATCH))
    t0 = time.perf_counter()
    trainer.run(steps)
    wall = time.perf_counter() - t0

    # eval accuracy on held-out batches: train=False normalization with the
    # EMA BatchNorm statistics the training run accumulated
    predict = get_task("cifar_cnn").make_predict(exp)
    params = eval_params(trainer.state, exp)
    correct = total = 0
    for i in range(4):
        b = make_image_batch(TASK, 99, i, 0, 32)
        logits = predict(params, trainer.state.model_state, b)
        correct += (np.asarray(jax.numpy.argmax(logits, -1)) ==
                    np.asarray(b["label"])).sum()
        total += 32
    return correct / total, trainer.executed_steps, wall, trainer


def run(fast: bool = True) -> List[str]:
    steps = 80 if fast else 240
    depth = 14 if fast else 26          # reduced ResNet (6n+2 family)
    rows = []
    acc, n, wall, tr0 = _train_resnet(depth, E2TrainConfig(), steps)
    rows.append(csv_row(f"tab4/resnet{depth}_smb", wall / max(n, 1) * 1e6,
                        f"acc={acc:.4f};{energy_fields(tr0, steps=steps)}"))
    e2 = E2TrainConfig(smd=SMDConfig(True),
                       slu=SLUConfig(True, alpha=5e-3, target_skip=0.2),
                       psg=PSGConfig(True, swa=False))
    acc2, n2, wall2, tr2 = _train_resnet(depth, e2, 2 * steps,
                                         optimizer="psg", lr=0.03)
    rows.append(csv_row(f"tab4/resnet{depth}_e2train",
                        wall2 / max(n2, 1) * 1e6,
                        f"acc={acc2:.4f};{energy_fields(tr2, steps=steps)};"
                        f"paper=0.8027;"
                        f"measured_psg_fallback={tr2.measured_psg_fallback()}"))

    # MobileNetV2 (compact backbone, paper's last Tab. 4 block) — fwd-only
    # smoke at bench scale: verify the compact arch runs under the harness
    from repro.models import resnet as R
    pmv, smv = R.init_mobilenetv2(jax.random.PRNGKey(2))
    b = make_image_batch(TASK, 0, 0, 0, 8)
    t0 = time.perf_counter()
    logits, _, _ = R.mobilenetv2_fwd(pmv, smv, b["image"])
    wallm = (time.perf_counter() - t0) * 1e6
    rows.append(csv_row("tab4/mobilenetv2_fwd", wallm,
                        f"logits_finite={bool(np.isfinite(np.asarray(logits)).all())}"))
    return rows
