"""Plain reference of the CIFAR MobileNetV2 training step, independent of
``src/``.

It follows the published model description and the E2-Train techniques
as the configuration and mix files state them, beside the ResNet
reference (``cnn.py``), whose PSG matmul, SLU gate, learning rate and
dtype helpers it uses:

* MobileNetV2 (Sandler et al. 2018, with the configuration's ``assumed``
  CIFAR strides; the block table is ``bench/families/cifar_mbv2.BLOCKS``):
  a 3x3 stem, BatchNorm with batch statistics, relu6; per block a 1x1
  expansion, a 3x3 depthwise conv (stride 1 or 2) and a linear 1x1
  projection, each followed by BatchNorm (relu6 after the first two), and
  an identity shortcut where the stride is 1 and the width unchanged;
  then a 1x1 head to 1280 channels, BatchNorm, relu6, global average
  pooling and the classifier;
* SLU: one weight-shared LSTM gate decides, per step, whether each of
  the identity-shortcut blocks runs (``lax.cond``, straight-through
  factor on the branch, never skipping the first and last gated block
  where the mix says so); the other blocks always run;
* PSG on the stem and 1x1 convs as ``cnn.psg_matmul``; on the depthwise
  convs: forward on the ``bits_x`` grid, the input gradient from the
  ``bits_g`` output gradient, and the weight gradient written element by
  element: for each tap ``t`` and channel ``c`` the MSB predictor product
  ``sum_m codes_msb(P_t)[m, c] * codes_msb(gy)[m, c]`` and the full code
  product, the predictor's sign where its magnitude clears ``beta *
  max``, else the full product's (Eq. 2).

A depthwise weight is ``(9, C)``, tap ``t = 3*i + j``.  Every array is
held in ``dtype``: float32 with every product at ``HIGHEST`` precision
for the reference, bfloat16 for its control.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.families.cifar_mbv2 import layout
from bench.reference.cnn import (BN_EPS, BN_MOMENTUM, conv, fake_quant,
                                 gate, learning_rate, mm, psg_matmul, qcodes,
                                 to_dtype)


def batchnorm(p, s, x):
    """BatchNorm with batch statistics, as ``cnn.batchnorm``, but with the
    variance as ``jnp.var`` forms it: the same forward, and a backward
    that rounds as the program's does, which matters where BatchNorm
    normalises over a few values and PSG turns each rounding into a
    sign."""
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    y = (x - mu) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mu,
           "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    return y, new


# ---------------------------------------------------------------------------
# depthwise conv
# ---------------------------------------------------------------------------


def taps(x, stride: int):
    """``(B, H', W', 9, C)``: the nine windows of the padded input at each
    output pixel, strided before the gather."""
    B, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return jnp.stack([xp[:, i:i + H:stride, j:j + W:stride, :]
                      for i in range(3) for j in range(3)], axis=-2)


def depthwise(x, w, stride: int):
    return jnp.sum(taps(x, stride) * w, axis=-2)


def psg_depthwise(psg: Dict[str, Any], stride: int):
    """``depthwise(x, w)`` with the PSG forward and backward of the mix's
    settings (module doc)."""
    bx, bg = psg["bits_x"], psg["bits_g"]
    bxm, bgm, beta = psg["bits_x_msb"], psg["bits_g_msb"], psg["beta"]

    def product(x, gy, bits_x, bits_g):
        p = taps(qcodes(x, bits_x)[0], stride)             # (B,H',W',9,C)
        g = qcodes(gy, bits_g)[0][..., None, :]            # (B,H',W',1,C)
        return jnp.sum(p * g, axis=(0, 1, 2))              # (9, C)

    @jax.custom_vjp
    def f(x, w):
        return depthwise(fake_quant(x, bx), fake_quant(w, bx), stride)

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, gy):
        x, w = res
        wq = fake_quant(w, bx)
        _, vjp = jax.vjp(lambda a: depthwise(a, wq, stride), x)
        (dx,) = vjp(fake_quant(gy, bg))
        g_msb = product(x, gy, bxm, bgm)
        g_full = product(x, gy, bx, bg)
        tau = beta * jnp.max(jnp.abs(g_msb))
        sign = jnp.where(jnp.abs(g_msb) >= tau, jnp.sign(g_msb),
                         jnp.sign(g_full))
        return dx, sign.astype(w.dtype)

    f.defvjp(fwd, bwd)
    return f


def plain_depthwise(stride: int):
    return lambda x, w: depthwise(x, w, stride)


# ---------------------------------------------------------------------------
# MobileNetV2
# ---------------------------------------------------------------------------


def _branch(matmul, dw, blk, bst, h):
    y, n1 = batchnorm(blk["bn1"], bst["bn1"],
                      conv(matmul, blk["expand"]["w"], h, 1))
    y = jax.nn.relu6(y)
    y, n2 = batchnorm(blk["bn2"], bst["bn2"], dw(y, blk["dw"]))
    y = jax.nn.relu6(y)
    y, n3 = batchnorm(blk["bn3"], bst["bn3"],
                      conv(matmul, blk["project"]["w"], y, 1))
    return y, {"bn1": n1, "bn2": n2, "bn3": n3}


def mbv2_forward(arch, e2, matmul, dws, p, s, x, rng, flips):
    """Returns (logits, new BN state, aux).  ``dws[stride]`` is the
    depthwise conv of that stride; ``flips[b]`` inverts the sampled SLU
    decision of block ``b`` (see ``bench/correct.py``)."""
    slu = e2["slu"]
    slu_on = slu["enabled"]
    gp = p.get("slu_gate")
    dt = x.dtype
    blocks = layout(arch)
    gated = [i for i, b in enumerate(blocks) if b["residual"]]
    ends = (gated[0], gated[-1]) if slu["never_skip_first_last"] else ()

    h, st_stem = batchnorm(p["stem_bn"], s["stem_bn"],
                           conv(matmul, p["stem"]["w"], x, 3))
    h = jax.nn.relu6(h)
    zeros = jnp.zeros((slu.get("gate_hidden", 10),), dt)
    gst = (zeros, zeros)
    new_s = {"stem_bn": st_stem, "blocks": []}
    info = []
    one = jnp.ones((), dt)
    for i, b in enumerate(blocks):
        blk, bst = p["blocks"][i], s["blocks"][i]
        dw = dws[b["stride"]]
        if b["residual"] and slu_on:
            pk, gst = gate(gp, h, gst, slu)
            u = jax.random.uniform(jax.random.fold_in(rng, i))
            keep = ((u < pk.astype(u.dtype)) != flips[i]) | (i in ends)
            g_st = 1.0 + pk - lax.stop_gradient(pk)

            def run(op, blk=blk, dw=dw):
                h, bst = op
                y, nb = _branch(matmul, dw, blk, bst, h)
                return h + g_st * y, nb

            h, nb = lax.cond(keep, run, lambda op: op, (h, bst))
            info.append((pk, keep.astype(dt), u.astype(dt)))
        else:
            y, nb = _branch(matmul, dw, blk, bst, h)
            h = h + y if b["residual"] else y
            info.append((one, one, one))
        new_s["blocks"].append(nb)
    h, st_head = batchnorm(p["head_bn"], s["head_bn"],
                           conv(matmul, p["head"]["w"], h, 1))
    h = jax.nn.relu6(h)
    new_s["head_bn"] = st_head
    pooled = jnp.mean(h, axis=(1, 2))
    logits = mm(pooled, p["fc_w"]) + p["fc_b"]
    keep_p, executed, u = (jnp.stack([inf[j] for inf in info])
                           for j in range(3))
    aux = {"keep_p": keep_p, "executed": executed, "u": u,
           "reg": jnp.mean(keep_p) if slu_on else jnp.ones((), dt)}
    return logits, new_s, aux


# ---------------------------------------------------------------------------
# the training step (the contract of cnn.make_step and cnn.run_steps)
# ---------------------------------------------------------------------------


def make_step(arch: Dict[str, Any], e2: Dict[str, Any],
              train: Dict[str, Any], dtype=jnp.float32):
    """``step(params, momentum, bn, batch, step, flips, seed) -> (params,
    momentum, bn, out)``: one training step on one batch at nominal step
    ``step``.  ``out`` holds the loss, the gradient before the optimizer,
    the SLU decisions, draws and gate probabilities."""
    psg = e2["psg"]
    psg_on = psg["enabled"]
    matmul = psg_matmul(psg) if psg_on else mm
    dws = {s: psg_depthwise(psg, s) if psg_on else plain_depthwise(s)
           for s in (1, 2)}
    slu_on = e2["slu"]["enabled"]
    alpha = e2["slu"]["alpha"] if slu_on else 0.0

    def loss_fn(params, bn, batch, rng, flips):
        x = batch["image"].astype(dtype)
        logits, new_bn, aux = mbv2_forward(arch, e2, matmul, dws, params,
                                           bn, x, rng, flips)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None],
                                            axis=-1))
        total = nll + alpha * aux["reg"].astype(jnp.float32)
        return total, (new_bn, aux)

    @jax.jit
    def step(params, momentum, bn, batch, step, flips, seed):
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        with jax.default_matmul_precision("highest"):
            (loss, (new_bn, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, bn, batch, rng, flips)
            raw = grads
            lr = learning_rate(train, step).astype(dtype)
            wd = jnp.asarray(train["weight_decay"], dtype)
            if train["optimizer"] == "psg":
                grads = jax.tree.map(jnp.sign, grads)
                new_m = grads
                new_p = jax.tree.map(lambda p, g: p - lr * (g + wd * p),
                                     params, grads)
            elif train["optimizer"] == "sgdm":
                mu = jnp.asarray(train["momentum"], dtype)
                new_m = jax.tree.map(lambda m, g, p: mu * m + g + wd * p,
                                     momentum, grads, params)
                new_p = jax.tree.map(lambda p, m: p - lr * m, params, new_m)
            else:
                raise ValueError(f"optimizer {train['optimizer']!r} has no "
                                 "reference")
        out = {"loss": loss, "grads_raw": raw, "executed": aux["executed"],
               "keep_p": aux["keep_p"], "u": aux["u"]}
        return new_p, new_m, new_bn, out

    return step


def n_gate_slots(arch: Dict[str, Any]) -> int:
    """Length of the ``flips`` vector: one slot per block (17)."""
    return len(layout(arch))


_STEPS: Dict[str, Any] = {}      # compiled steps, by settings and dtype


def run_steps(arch, e2, train, params, momentum, bn, batches, steps,
              dtype=jnp.float32, choose=None) -> Tuple[Any, Any, Any, list]:
    """Follow ``steps`` (nominal step ids) over ``batches``.  ``choose``,
    where given, picks among candidate outputs of one step (see
    ``bench/correct.py``); returns (params, momentum, bn, per-step outs)."""
    key = json.dumps([arch, e2, {k: v for k, v in train.items()
                                 if k != "seed"}, jnp.dtype(dtype).name],
                     sort_keys=True)
    if key not in _STEPS:
        _STEPS[key] = make_step(arch, e2, train, dtype)
    step_fn, seed = _STEPS[key], np.uint32(train["seed"])
    params, momentum, bn = (to_dtype(t, dtype) for t in (params, momentum, bn))
    slots = n_gate_slots(arch)
    outs = []
    for i, (batch, nominal) in enumerate(zip(batches, steps)):
        flips = jnp.zeros((slots,), bool)
        res = step_fn(params, momentum, bn, batch, nominal, flips, seed)
        if choose is not None:
            res = choose(i, res, lambda f: step_fn(params, momentum, bn,
                                                   batch, nominal, f, seed))
        params, momentum, bn, out = res
        outs.append(out)
    return params, momentum, bn, outs
