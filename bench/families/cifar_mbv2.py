"""The CIFAR MobileNetV2 family: the program's experiment for a MobileNetV2
configuration and the arithmetic of the model (FLOPs per image, the PSG
matmul sites of its stem and 1x1 convs, its depthwise sites).

Seeds, optimizer settings, weights and the optimizer buffer are the CIFAR
CNN family's (``cifar_cnn.py``): its init rule ``std = gain /
sqrt(shape[-2])`` gives a 1x1 conv weight ``(cin, cout)`` fan-in ``cin``
and the depthwise weight ``(9, C)`` fan-in 9.
"""
from __future__ import annotations

from typing import Any, Dict, List

from bench.families import cifar_cnn
from bench.families.cifar_cnn import (optimizer_buffer, seeds,  # noqa: F401
                                      train_settings)

# Sandler et al. 2018, Table 2, at width 1.0: (expansion t, out channels
# c, blocks n, stride s of the first block), with the CIFAR port's stride
# 1 on the first 24-channel stage; a 32-channel stride-1 stem before and
# a 1x1 head to 1280 channels after
BLOCKS = ((1, 16, 1, 1), (6, 24, 2, 1), (6, 32, 3, 2), (6, 64, 4, 2),
          (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))
STEM, HEAD = 32, 1280


def layout(arch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per block: input width ``cin``, expanded width ``hidden``, ``cout``,
    ``stride``, input and output extents ``in_hw``/``out_hw``, and
    ``residual`` (an identity shortcut: stride 1 and unchanged width,
    the blocks SLU gates)."""
    if arch["kind"] != "mobilenetv2":
        raise ValueError(f"model kind {arch['kind']!r} is not mobilenetv2")
    out, cin, hw = [], STEM, arch["image_hw"]
    for t, c, n, s in BLOCKS:
        for b in range(n):
            stride = s if b == 0 else 1
            out.append({"cin": cin, "hidden": cin * t, "cout": c,
                        "stride": stride, "in_hw": hw,
                        "out_hw": -(-hw // stride),
                        "residual": stride == 1 and cin == c})
            cin, hw = c, -(-hw // stride)
    return out


def program_experiment(config: Dict[str, Any], mix: Dict[str, Any],
                       chips: int, train_seed: int):
    """The program's ``Experiment``: the CIFAR CNN family's, with the
    model named ``mobilenetv2`` (the program selects the backbone by name
    and reads no depth or width for it)."""
    shim = dict(config, arch=dict(config["arch"], depth=0, width=STEM))
    return cifar_cnn.program_experiment(shim, mix, chips, train_seed)


def init_state(config: Dict[str, Any], exp, weights_seed: int):
    """The CIFAR CNN family's weights, once the program is known to train
    what the mix asks of MobileNetV2: an SLU gate where SLU is on, and a
    PSG depthwise conv where PSG is on.  A program without them stops
    here, at set-up, with an error."""
    import jax
    from repro.core import psg
    from repro.training.train_step import init_train_state
    shapes = jax.eval_shape(lambda k: init_train_state(k, exp),
                            jax.random.PRNGKey(0))
    missing = []
    if exp.e2.slu.enabled and "slu_gate" not in shapes.params:
        missing.append("SLU gate")
    if exp.e2.psg.enabled and not hasattr(psg, "depthwise"):
        missing.append("PSG depthwise conv")
    if missing:
        raise RuntimeError("the program's MobileNetV2 has no "
                           + " and no ".join(missing))
    return cifar_cnn.init_state(config, exp, weights_seed)


# ---------------------------------------------------------------------------
# arithmetic of the model (checked against the numbers pinned in the
# configuration file by tests/bench)
# ---------------------------------------------------------------------------


def conv_sites(arch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The stem and every 1x1 conv of one image's forward as matmul sites
    (output extent ``hw``, ``din = k*k*cin``, ``dout``, whether SLU may
    skip it); each is followed by a BatchNorm."""
    hw = arch["image_hw"]
    sites = [{"hw": hw, "din": 9 * arch["channels"], "dout": STEM,
              "gated": False}]
    blocks = layout(arch)
    for b in blocks:
        sites.append({"hw": b["in_hw"], "din": b["cin"],
                      "dout": b["hidden"], "gated": b["residual"]})
        sites.append({"hw": b["out_hw"], "din": b["hidden"],
                      "dout": b["cout"], "gated": b["residual"]})
    sites.append({"hw": blocks[-1]["out_hw"], "din": blocks[-1]["cout"],
                  "dout": HEAD, "gated": False})
    return sites


def dw_sites(arch: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The 17 depthwise convs: ``in_hw``, ``out_hw``, channels ``C``,
    ``stride``, and whether SLU may skip them."""
    return [{"in_hw": b["in_hw"], "out_hw": b["out_hw"], "C": b["hidden"],
             "stride": b["stride"], "gated": b["residual"]}
            for b in layout(arch)]


def forward_macs(arch: Dict[str, Any]) -> float:
    """Forward multiply-accumulates per image: the stem and 1x1 convs, 9
    per depthwise output element, one per BatchNorm output element (scale
    and shift; every conv and depthwise conv has one), the classifier."""
    macs = 0.0
    for s in conv_sites(arch):
        out = s["hw"] * s["hw"] * s["dout"]
        macs += out * s["din"] + out
    for d in dw_sites(arch):
        macs += 10.0 * d["out_hw"] * d["out_hw"] * d["C"]
    return macs + HEAD * arch["classes"]


def flop_per_image(arch: Dict[str, Any]) -> float:
    """Training FLOPs per image of the dense model: 6 x forward MACs."""
    return 6.0 * forward_macs(arch)


def param_count(arch: Dict[str, Any]) -> int:
    """Parameters of the backbone (without the SLU gate): conv and
    depthwise weights, BatchNorm scales and biases, the classifier."""
    n = sum(s["din"] * s["dout"] + 2 * s["dout"] for s in conv_sites(arch))
    n += sum(9 * d["C"] + 2 * d["C"] for d in dw_sites(arch))
    return n + HEAD * arch["classes"] + arch["classes"]


def psg_sites(arch: Dict[str, Any], batch: int) -> List[Dict[str, Any]]:
    """The PSG weight-gradient matmuls of one training step: rows ``N =
    batch * hw * hw``, ``din``, ``dout``, and whether SLU may skip them."""
    return [{"N": batch * s["hw"] * s["hw"], "din": s["din"],
             "dout": s["dout"], "gated": s["gated"]}
            for s in conv_sites(arch)]
