"""The CIFAR CNN family: how a configuration and a mix become the program's
experiment, its train state and its feed, and what the arithmetic of the
model is (FLOPs per image, PSG weight-gradient sites).

The benchmark makes the weights itself, from the seed, in one jitted call,
in the program's parameter layout; the program and the reference both
start from them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


TRAIN_SEED = 0


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for the weights and the data, derived from
    the benchmark's ``--seed``.  The run's own seed (the SMD schedule and
    the SLU draws) is fixed: the program bakes it into its compiled chunk
    program, so a seed of its own per run would compile anew every run,
    and every seed keeps the same steps."""
    w, d = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"weights": int(w) % 2 ** 31, "data": int(d) % 2 ** 31,
            "train": TRAIN_SEED}


def train_settings(config: Dict[str, Any], mix: Dict[str, Any],
                   train_seed: int) -> Dict[str, Any]:
    """The optimizer settings of a cell: the mix's, or the configuration's
    paper settings where the mix says ``"train": "paper"``."""
    train = config["paper_train"] if mix["train"] == "paper" else mix["train"]
    return dict(train, seed=train_seed)


def program_experiment(config: Dict[str, Any], mix: Dict[str, Any],
                       chips: int, train_seed: int):
    """The program's ``Experiment`` for one cell."""
    from repro.core.config import (E2TrainConfig, Experiment, ModelConfig,
                                   PSGConfig, SLUConfig, SMDConfig,
                                   TrainConfig)
    arch, e2 = config["arch"], mix["e2train"]
    model = ModelConfig(
        name=config["program_name"], family="cnn",
        num_layers=arch["depth"], d_model=arch["width"],
        num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=arch["classes"],
        glu=False, dtype="float32")
    slu = {k: v for k, v in e2["slu"].items()}
    psg = {k: v for k, v in e2["psg"].items()}
    e2cfg = E2TrainConfig(smd=SMDConfig(**e2["smd"]), slu=SLUConfig(**slu),
                          psg=PSGConfig(**psg))
    t = train_settings(config, mix, train_seed)
    train = TrainConfig(
        global_batch=int(mix["batch_per_chip"]) * chips, lr=t["lr"],
        schedule=t["schedule"], total_steps=t["total_steps"],
        decay_points=tuple(t["decay_points"]),
        decay_factor=t["decay_factor"], momentum=t["momentum"],
        weight_decay=t["weight_decay"], optimizer=t["optimizer"],
        seed=train_seed, microbatches=1)
    return Experiment(model=model, e2=e2cfg, train=train, task="cifar_cnn")


def optimizer_buffer(opt_state):
    """The optimizer's gradient buffer in the program's optimizer state:
    the last sign gradient under sign SGD, the momentum sum under SGD."""
    return opt_state["momentum"]


def _leaf_name(path) -> str:
    key = path[-1]
    return str(getattr(key, "key", getattr(key, "name", key)))


def init_state(config: Dict[str, Any], exp, weights_seed: int):
    """The program's ``TrainState`` with weights made by the benchmark from
    the seed, on the device, in one jitted call: truncated normal weights
    of standard deviation ``gain / sqrt(fan_in)``, BatchNorm scales and
    running variances 1, biases and running means 0, optimizer state as
    the program's optimizer starts it."""
    from repro.optim.api import make_optimizer
    from repro.optim.swa import swa_init
    from repro.training.train_step import init_train_state
    rules = config["init"]
    shapes = jax.eval_shape(lambda k: init_train_state(k, exp),
                            jax.random.PRNGKey(0))

    def weight(key, path, sds):
        name = _leaf_name(path)
        if name in rules["ones"]:
            return jnp.ones(sds.shape, sds.dtype)
        if name in rules["zeros"]:
            return jnp.zeros(sds.shape, sds.dtype)
        gain = rules["gain"].get(name, rules["default_gain"])
        std = gain / math.sqrt(sds.shape[-2])
        return (std * jax.random.truncated_normal(key, -2.0, 2.0, sds.shape)
                ).astype(sds.dtype)

    def make(key):
        flat, tdef = jax.tree_util.tree_flatten_with_path(shapes.params)
        params = tdef.unflatten([weight(jax.random.fold_in(key, i), p, s)
                                 for i, (p, s) in enumerate(flat)])
        model_state = jax.tree_util.tree_map_with_path(
            lambda p, s: (jnp.ones if _leaf_name(p) in rules["state_ones"]
                          else jnp.zeros)(s.shape, s.dtype),
            shapes.model_state)
        swa = None if shapes.swa is None else swa_init(params)
        return shapes._replace(params=params,
                               opt=make_optimizer(exp.train).init(params),
                               swa=swa, step=jnp.zeros((), jnp.int32),
                               model_state=model_state)

    return jax.jit(make)(jax.random.PRNGKey(weights_seed))


# ---------------------------------------------------------------------------
# arithmetic of the model (checked against the numbers pinned in the
# configuration files by tests/bench)
# ---------------------------------------------------------------------------


def conv_sites(arch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every conv of one image's forward of a 6n+2 ResNet as a matmul site:
    output pixels ``hw`` x ``hw``, ``din = k*k*cin``, ``dout``, whether an
    SLU gate may skip it and whether a BatchNorm follows it (not after a
    projection shortcut)."""
    if arch["kind"] != "resnet":
        raise ValueError(f"model kind {arch['kind']!r} has no arithmetic")
    sites: List[Dict[str, Any]] = []

    def conv(hw, k, cin, cout, gated=False, bn=True):
        sites.append({"hw": hw, "din": k * k * cin, "dout": cout,
                      "gated": gated, "bn": bn})

    hw = arch["image_hw"]
    n, width = (arch["depth"] - 2) // 6, arch["width"]
    conv(hw, 3, arch["channels"], width)
    cin = width
    for stage, cout in enumerate((width, 2 * width, 4 * width)):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            hw //= stride
            proj = b == 0 and cin != cout
            conv(hw, 3, cin, cout, not proj)
            conv(hw, 3, cout, cout, not proj)
            if proj:
                conv(hw, 1, cin, cout, bn=False)
            cin = cout
    return sites


def forward_macs(arch: Dict[str, Any]) -> float:
    """Forward multiply-accumulates per image: convs, one per BatchNorm
    output element (scale and shift), the classifier."""
    macs = 0.0
    for s in conv_sites(arch):
        out = s["hw"] * s["hw"] * s["dout"]
        macs += out * s["din"] + (out if s["bn"] else 0)
    return macs + 4 * arch["width"] * arch["classes"]


def flop_per_image(arch: Dict[str, Any]) -> float:
    """Training FLOPs per image of the dense model: forward, input gradient
    and weight gradient, two FLOPs per MAC: 6 x forward MACs."""
    return 6.0 * forward_macs(arch)


def psg_sites(arch: Dict[str, Any], batch: int) -> List[Dict[str, Any]]:
    """The PSG weight-gradient matmuls of one training step: rows
    ``N = batch * hw * hw``, ``din``, ``dout``, and whether SLU may skip
    them."""
    return [{"N": batch * s["hw"] * s["hw"], "din": s["din"],
             "dout": s["dout"], "gated": s["gated"]}
            for s in conv_sites(arch)]
