"""Plain reference of the CIFAR CNN training step, independent of ``src/``.

It follows the published model descriptions and the E2-Train techniques as
the configuration and mix files state them:

* ResNet (6n+2, He et al. 2016 §4.2): 3x3 convs, BatchNorm with batch
  statistics in training, ReLU, identity shortcuts and 1x1 stride-2
  projection shortcuts where the width changes;
* SLU: one weight-shared LSTM gate over the batch-pooled block input
  decides, per block and step, whether an identity-shortcut block runs,
  with a straight-through factor on the branch and ``alpha`` times the
  mean keep probability added to the loss;
* PSG: forward products on the ``bits_x`` grid; the input gradient from
  the ``bits_g`` output gradient; the weight gradient is the sign of the
  MSB predictor product (``bits_x_msb`` x ``bits_g_msb`` codes) where its
  magnitude clears ``beta * max``, else the sign of the full code product
  (Eq. 2); every gradient leaf then goes through ``sign``;
* optimizers: SGD with momentum and weight decay, and sign SGD (``psg``).

A conv weight is the ``(k*k*cin, cout)`` matrix of a channel-major patch
vector, the layout in which the benchmark makes the weights.  Every array
is held in ``dtype``: float32 with every product at ``HIGHEST`` precision
for the reference, bfloat16 for its control.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5
BN_MOMENTUM = 0.9


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# PSG
# ---------------------------------------------------------------------------


def qcodes(x, bits):
    """Symmetric per-tensor codes on the ``bits`` grid and their scale."""
    lim = 2.0 ** (bits - 1) - 1.0
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / lim
    return jnp.clip(jnp.round(x / s), -lim, lim), s


def fake_quant(x, bits):
    q, s = qcodes(x, bits)
    return q * s


def psg_matmul(psg: Dict[str, Any]):
    """``x2 @ w`` with the PSG forward and backward of the mix's settings."""
    bx, bg = psg["bits_x"], psg["bits_g"]
    bxm, bgm, beta = psg["bits_x_msb"], psg["bits_g_msb"], psg["beta"]

    @jax.custom_vjp
    def f(x2, w):
        return mm(fake_quant(x2, bx), fake_quant(w, bx))

    def fwd(x2, w):
        return f(x2, w), (x2, w)

    def bwd(res, gy):
        x2, w = res
        dx = mm(fake_quant(gy, bg), fake_quant(w, bx).T)
        g_msb = mm(qcodes(x2, bxm)[0].T, qcodes(gy, bgm)[0])
        g_full = mm(qcodes(x2, bx)[0].T, qcodes(gy, bg)[0])
        tau = beta * jnp.max(jnp.abs(g_msb))
        sign = jnp.where(jnp.abs(g_msb) >= tau, jnp.sign(g_msb),
                         jnp.sign(g_full))
        return dx, sign.astype(w.dtype)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def conv(matmul, w, x, k: int, stride: int = 1):
    """SAME conv (padding k // 2) as a matmul over channel-major patches."""
    pad = k // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    patches = lax.conv_general_dilated_patches(
        xp, (k, k), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    b, ho, wo, f = patches.shape
    return matmul(patches.reshape(b * ho * wo, f), w).reshape(b, ho, wo, -1)


def batchnorm(p, s, x):
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2))
    y = (x - mu) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mu,
           "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    return y, new


def gate(gp, x, state, slu):
    """SLU gate: batch-pooled features -> projection -> LSTM -> keep prob."""
    pooled = jnp.mean(x, axis=(0, 1, 2))
    pooled = jnp.pad(pooled, (0, gp["proj"].shape[0] - pooled.shape[0]))
    h_prev, c_prev = state
    g = mm(mm(pooled, gp["proj"]), gp["lstm_wx"]) + mm(h_prev, gp["lstm_wh"]) \
        + gp["lstm_b"]
    i_t, f_t, o_t, u_t = jnp.split(g, 4)
    c = jax.nn.sigmoid(f_t + 1.0) * c_prev + jax.nn.sigmoid(i_t) * jnp.tanh(u_t)
    h = jax.nn.sigmoid(o_t) * jnp.tanh(c)
    logit = (mm(h, gp["head_w"]) + gp["head_b"])[0]
    p = jnp.clip(jax.nn.sigmoid(logit), slu["min_keep_prob"], 1.0)
    return p, (h, c)


# ---------------------------------------------------------------------------
# ResNet (6n+2)
# ---------------------------------------------------------------------------


def _branch(matmul, blk, bst, h, stride):
    y, n1 = batchnorm(blk["bn1"], bst["bn1"],
                      conv(matmul, blk["conv1"]["w"], h, 3, stride))
    y = jax.nn.relu(y)
    y, n2 = batchnorm(blk["bn2"], bst["bn2"],
                      conv(matmul, blk["conv2"]["w"], y, 3))
    return y, {"bn1": n1, "bn2": n2}


def resnet_forward(arch, e2, matmul, p, s, x, rng, flips):
    """Returns (logits, new BN state, aux).  ``flips[b]`` inverts the
    sampled SLU decision of block ``b`` (see ``bench/correct.py``)."""
    n = (arch["depth"] - 2) // 6
    n_blocks = 3 * n
    slu = e2["slu"]
    slu_on = slu["enabled"]
    gp = p.get("slu_gate")
    dt = x.dtype

    def gated(blk, bst, h, gst, glob):
        if not slu_on:
            y, nb = _branch(matmul, blk, bst, h, 1)
            one = jnp.ones((), dt)
            return jax.nn.relu(h + y), nb, gst, (one, one, one)
        pk, gst = gate(gp, h, gst, slu)
        u = jax.random.uniform(jax.random.fold_in(rng, glob))
        keep = (u < pk.astype(u.dtype)) != flips[glob]
        if slu["never_skip_first_last"]:
            keep = keep | (glob == 0) | (glob == n_blocks - 1)
        g_st = 1.0 + pk - lax.stop_gradient(pk)

        def run(op):
            h, bst = op
            y, nb = _branch(matmul, blk, bst, h, 1)
            return h + g_st * y, nb

        h, nb = lax.cond(keep, run, lambda op: op, (h, bst))
        return jax.nn.relu(h), nb, gst, (pk, keep.astype(dt), u)

    h, st_stem = batchnorm(p["stem_bn"], s["stem_bn"],
                           conv(matmul, p["stem"]["w"], x, 3))
    h = jax.nn.relu(h)
    zeros = jnp.zeros((slu.get("gate_hidden", 10),), dt)
    gst = (zeros, zeros)
    new_s = {"stem_bn": st_stem, "stages": []}
    info = []
    for stage in range(3):
        sp, ss = p["stages"][stage], s["stages"][stage]
        glob = stage * n
        blk, bst = sp["trans"], ss["trans"]
        if "down" in blk:
            stride = 2 if stage > 0 else 1
            short = conv(matmul, blk["down"]["conv"]["w"], h, 1, stride)
            y, nb = _branch(matmul, blk, bst, h, stride)
            h = jax.nn.relu(short + y)
            one = jnp.ones((1,), dt)
            info.append((one, one, one))
        else:
            h, nb, gst, inf = gated(blk, bst, h, gst, glob)
            info.append(tuple(v[None] for v in inf))
        nss = {"trans": nb}

        @jax.checkpoint
        def body(carry, xs):
            h, gst = carry
            blk, bst, g = xs
            h, nb, gst, inf = gated(blk, bst, h, gst, g)
            return (h, gst), (nb, inf)

        (h, gst), (rest_s, inf) = lax.scan(
            body, (h, gst), (sp["rest"], ss["rest"],
                             jnp.arange(glob + 1, glob + n)))
        nss["rest"] = rest_s
        info.append(inf)
        new_s["stages"].append(nss)
    pooled = jnp.mean(h, axis=(1, 2))
    logits = mm(pooled, p["fc_w"]) + p["fc_b"]
    keep_p, executed, u = (jnp.concatenate([i[j] for i in info])
                           for j in range(3))
    aux = {"keep_p": keep_p, "executed": executed, "u": u,
           "reg": jnp.mean(keep_p) if slu_on else jnp.ones((), dt)}
    return logits, new_s, aux




# ---------------------------------------------------------------------------
# the training step
# ---------------------------------------------------------------------------


def learning_rate(train, step):
    lr = jnp.float32(train["lr"])
    if train["schedule"] == "step":
        for frac in train["decay_points"]:
            lr = jnp.where(step >= frac * train["total_steps"],
                           lr * train["decay_factor"], lr)
    elif train["schedule"] != "constant":
        raise ValueError(f"schedule {train['schedule']!r} has no reference")
    return lr


def make_step(arch: Dict[str, Any], e2: Dict[str, Any],
              train: Dict[str, Any], dtype=jnp.float32):
    """``step(params, momentum, bn, batch, step, flips, seed) -> (params,
    momentum, bn, out)``: one training step on one batch at nominal step
    ``step``.  ``out`` holds the loss, the gradient before the optimizer,
    the SLU decisions, draws and gate probabilities."""
    if arch["kind"] != "resnet":
        raise ValueError(f"model kind {arch['kind']!r} has no reference")
    psg_on = e2["psg"]["enabled"]
    matmul = psg_matmul(e2["psg"]) if psg_on else mm
    slu_on = e2["slu"]["enabled"]
    alpha = e2["slu"]["alpha"] if slu_on else 0.0

    def loss_fn(params, bn, batch, rng, flips):
        x = batch["image"].astype(dtype)
        logits, new_bn, aux = resnet_forward(arch, e2, matmul, params, bn,
                                             x, rng, flips)
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
    """Length of the ``flips`` vector: one slot per block."""
    return 3 * ((arch["depth"] - 2) // 6)


def to_dtype(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype)
                        if np.issubdtype(np.asarray(a).dtype, np.floating)
                        else jnp.asarray(a), tree)


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
