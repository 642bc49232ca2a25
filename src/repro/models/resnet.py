"""Paper-faithful CIFAR backbones: ResNet-74 / ResNet-110 / MobileNetV2.

These are the models the paper actually trains (§4.1): CIFAR-style ResNets
(6n+2 layers; n=12 -> 74, n=18 -> 110, [He et al. 2016]) and MobileNetV2
scaled for 32x32 inputs.  E²-Train hooks are identical to the transformer
path: SLU gates every identity-shortcut block (the paper's granularity;
ResNet's projection transitions and MobileNetV2's stride-2 or widening
blocks always run), PSG routes the conv-as-matmul weight gradients and,
in MobileNetV2, the depthwise weight gradients (``psg.depthwise``), SMD
lives in the data pipeline.

Convs are implemented as im2col + ``psg.matmul`` so the PSG custom-vjp (and
later the Pallas kernel) applies to the conv backward exactly as the paper's
Eq. (4) describes (``g_w`` as a sum of input x output-grad inner products).

Structure (mirrors the transformer stack, DESIGN.md §Tasks):

* **Scanned stages.**  Each of the three CIFAR stages holds one unrolled
  *transition* block (``trans`` — owns the stride-2 spatial reduction and
  the 1x1 projection shortcut ``down`` when the channel count changes) plus
  the remaining ``n-1`` identical blocks with parameters stacked on a
  leading axis (``rest``), executed with ``jax.lax.scan``.  ResNet-110
  traces as 3 transition blocks + 3 scans of 17 instead of 54 unrolled
  blocks, so ``jax.jit`` of a full train step completes in seconds.  The
  SLU gate's LSTM state and the ``lax.cond`` hard skip are carried through
  the scan exactly like the LM path.
* **BatchNorm running statistics** live in a *state* tree parallel to the
  params (same ``stages``/``trans``/``rest`` shape): the forward threads
  them through the scan and returns the EMA-updated tree, so ``train=False``
  evaluation normalizes with learned statistics — and the optimizer never
  touches them (they are not parameters).
* ``resnet_fwd_ref`` keeps the per-block unrolled execution over the same
  parameter layout as the scan's semantics anchor (tests/test_resnet_scan).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import psg, slu
from repro.core.config import E2TrainConfig
from repro.kernels.psg_depthwise import depthwise_conv
from repro.models.layers import dense_init

Params = Dict[str, Any]

BN_MOMENTUM = 0.9           # running-stat EMA decay per executed train step


# ---------------------------------------------------------------------------
# conv via im2col (PSG-routable)
# ---------------------------------------------------------------------------


def init_conv(key, cin: int, cout: int, k: int = 3) -> Params:
    return {"w": dense_init(key, (k * k * cin, cout), jnp.float32, scale=1.41)}


def conv2d(p: Params, x: jnp.ndarray, k: int = 3, stride: int = 1) -> jnp.ndarray:
    """x: (B, H, W, C) -> (B, H', W', cout).

    With an active PSG context whose ``fused_conv`` resolves on (the
    default on the reference/interpret backends — see
    ``psg.fused_conv_active``), the conv runs through ``psg.conv2d`` — the
    fused implicit-GEMM Pallas kernels (``kernels/conv.py``) that gather
    the k x k patches inside the kernel in BOTH directions (forward, PSG
    weight gradient, and the implicit transposed-conv input gradient) and
    never write a ``(B*H'*W', k*k*C)`` patch tensor to HBM (DESIGN.md
    §Kernels).  Otherwise: materialized im2col + ``psg.matmul`` (the
    original PSG-routable formulation, kept as the correctness anchor and
    the Mosaic default pending real-TPU profiling).  Both model families
    share this entry point (the MobileNetV2 1x1 expand/project/head convs
    included).
    """
    cfg = psg.active_config()
    if psg.fused_conv_active(cfg):
        return psg.conv2d(x, p["w"], k=k, stride=stride)
    B, H, W, C = x.shape
    pad = k // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    patches = lax.conv_general_dilated_patches(
        xp, (k, k), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    Ho, Wo = patches.shape[1], patches.shape[2]
    y = psg.matmul(patches.reshape(B * Ho * Wo, k * k * C), p["w"])
    return y.reshape(B, Ho, Wo, -1)


def init_bn(c: int) -> Params:
    """Trainable affine only — running stats live in the state tree."""
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def init_bn_state(c: int) -> Params:
    return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def batchnorm(p: Params, s: Params, x: jnp.ndarray, train: bool = True
              ) -> Tuple[jnp.ndarray, Params]:
    """Returns (normalized x, new running-stat state).

    Train mode normalizes with batch statistics and moves the EMA toward
    them; eval mode normalizes with the stored statistics and leaves the
    state untouched.
    """
    if train:
        mu = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        new_s = {"mean": BN_MOMENTUM * s["mean"] + (1.0 - BN_MOMENTUM) * mu,
                 "var": BN_MOMENTUM * s["var"] + (1.0 - BN_MOMENTUM) * var}
    else:
        mu, var = s["mean"], s["var"]
        new_s = s
    y = (x - mu) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y, new_s


def _stack(trees: List[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# CIFAR ResNet (6n+2)
# ---------------------------------------------------------------------------


def resnet_depth_to_n(depth: int) -> int:
    assert (depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
    return (depth - 2) // 6


def _init_block(key, cin: int, cout: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"conv1": init_conv(k1, cin, cout), "bn1": init_bn(cout),
            "conv2": init_conv(k2, cout, cout), "bn2": init_bn(cout)}


def _init_block_state(cout: int) -> Params:
    return {"bn1": init_bn_state(cout), "bn2": init_bn_state(cout)}


def init_resnet(key, depth: int, num_classes: int = 10,
                e2: Optional[E2TrainConfig] = None,
                width: int = 16) -> Tuple[Params, Params]:
    """Returns (params, state): state is the BatchNorm running-stat tree."""
    n = resnet_depth_to_n(depth)
    e2 = e2 or E2TrainConfig()
    # fixed budget: stem + 3 x (trans, down, rest-fold base) + fc + gate
    keys = jax.random.split(key, 12)
    ki = iter(range(len(keys)))
    p: Params = {"stem": init_conv(keys[next(ki)], 3, width),
                 "stem_bn": init_bn(width), "stages": []}
    s: Params = {"stem_bn": init_bn_state(width), "stages": []}
    cin = width
    for stage, cout in enumerate((width, 2 * width, 4 * width)):
        trans = _init_block(keys[next(ki)], cin, cout)
        if cin != cout:
            trans["down"] = {"conv": init_conv(keys[next(ki)], cin, cout, k=1)}
        else:
            next(ki)
        sp: Params = {"trans": trans}
        ss: Params = {"trans": _init_block_state(cout)}
        if n > 1:
            rest_base = keys[next(ki)]
            sp["rest"] = _stack([_init_block(jax.random.fold_in(rest_base, b),
                                             cout, cout) for b in range(n - 1)])
            ss["rest"] = _stack([_init_block_state(cout) for _ in range(n - 1)])
        else:
            next(ki)
        p["stages"].append(sp)
        s["stages"].append(ss)
        cin = cout
    p["fc_w"] = dense_init(keys[next(ki)], (4 * width, num_classes), jnp.float32)
    p["fc_b"] = jnp.zeros((num_classes,))
    if e2.slu.enabled:
        # weight-shared gate on channel-pooled features, padded to max width
        p["slu_gate"] = slu.init_gate(keys[next(ki)], 4 * width, e2.slu)
    return p, s


def _block_branch(blk: Params, bst: Params, h: jnp.ndarray, stride: int,
                  train: bool) -> Tuple[jnp.ndarray, Params]:
    """conv-BN-relu-conv-BN residual branch; returns (branch, new bn state)."""
    y, ns1 = batchnorm(blk["bn1"], bst["bn1"],
                       conv2d(blk["conv1"], h, stride=stride), train)
    y = jax.nn.relu(y)
    y, ns2 = batchnorm(blk["bn2"], bst["bn2"], conv2d(blk["conv2"], y), train)
    return y, {"bn1": ns1, "bn2": ns2}


def _slu_skip(branch, h, bst, gate_params, gst, glob, force, e2, rng):
    """One identity-shortcut block under the SLU gate: the gate's keep
    probability from the block input, a Bernoulli draw folded from the
    block index ``glob`` (or ``force``), and ``lax.cond`` between
    ``h + g_st * branch`` and the identity.  ``branch(h, bst)`` returns
    (branch output, new BN state).  Returns (h, new_bn_state,
    new_gate_state, keep_prob, executed)."""
    pkeep, gst = slu.gate_apply(gate_params, h, gst, e2.slu)
    brng = jax.random.fold_in(rng, glob)
    keep = jax.random.bernoulli(brng, pkeep) | force
    g_st = 1.0 + pkeep - lax.stop_gradient(pkeep)   # straight-through factor

    def run(op):
        h, bst = op
        y, nbst = branch(h, bst)
        return h + g_st * y, nbst

    h, nbst = lax.cond(keep, run, lambda op: op, (h, bst))
    return h, nbst, gst, pkeep, keep.astype(jnp.float32)


def _gated_block(blk, bst, h, gate_params, gst, glob, n_blocks, e2, rng,
                 train: bool, slu_on: bool):
    """Stride-1 identity-shortcut block, SLU-gated when ``slu_on``.

    ``glob`` may be a traced scalar (the scan's block-index input); returns
    (h, new_bn_state, new_gate_state, keep_prob, executed).
    """
    if not slu_on:
        y, nbst = _block_branch(blk, bst, h, 1, train)
        return (jax.nn.relu(h + y), nbst, gst,
                jnp.float32(1.0), jnp.float32(1.0))
    force = ((glob == 0) | (glob == n_blocks - 1)) \
        if e2.slu.never_skip_first_last else jnp.bool_(False)
    h, nbst, gst, pkeep, ex = _slu_skip(
        lambda h, bst: _block_branch(blk, bst, h, 1, train), h, bst,
        gate_params, gst, glob, force, e2, rng)
    return jax.nn.relu(h), nbst, gst, pkeep, ex


def _transition_block(sp, ss, h, stage, gate_params, gst, glob, n_blocks,
                      e2, rng, train: bool, slu_on: bool):
    """First block of a stage.  With a projection shortcut it is never gated
    (the paper gates only identity-shortcut blocks); stage 0's transition is
    an ordinary stride-1 identity block and gates like the rest."""
    blk, bst = sp["trans"], ss["trans"]
    stride = 2 if stage > 0 else 1
    if "down" in blk:
        shortcut = conv2d(blk["down"]["conv"], h, k=1, stride=stride)
        y, nbst = _block_branch(blk, bst, h, stride, train)
        return (jax.nn.relu(shortcut + y), nbst, gst,
                jnp.float32(1.0), jnp.float32(1.0))
    return _gated_block(blk, bst, h, gate_params, gst, glob, n_blocks, e2,
                        rng, train, slu_on)


def resnet_fwd(p: Params, state: Params, x: jnp.ndarray, depth: int,
               e2: Optional[E2TrainConfig] = None,
               rng: Optional[jnp.ndarray] = None,
               train: bool = True
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Params]:
    """x: (B, 32, 32, 3) -> (logits, aux{slu_*}, new running-stat state).

    Per-stage ``lax.scan`` over the stacked ``rest`` blocks; the SLU gate
    state, the activations, and the BN statistics thread through the scan.
    """
    n = resnet_depth_to_n(depth)
    e2 = e2 or E2TrainConfig()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    slu_on = e2.slu.enabled and train and "slu_gate" in p
    gate_params = p.get("slu_gate")
    n_blocks = 3 * n

    # "cost:<group>" scopes are the attribution anchors the static audit
    # reads back out of the traced jaxpr (analysis/jaxpr_cost.py); group
    # names follow core/cost.py's layer prefixes (s{i}b0 -> s{i}.trans,
    # s{i}b{1..} -> s{i}.rest).
    with jax.named_scope("cost:stem"):
        h, ns_stem = batchnorm(p["stem_bn"], state["stem_bn"],
                               conv2d(p["stem"], x), train)
        h = jax.nn.relu(h)
    gst = slu.init_gate_state(e2.slu)
    new_state: Params = {"stem_bn": ns_stem, "stages": []}
    kps, exs = [], []
    for stage in range(3):
        sp, ss = p["stages"][stage], state["stages"][stage]
        glob = stage * n
        with jax.named_scope(f"cost:s{stage}.trans"):
            h, nbst, gst, kp, ex = _transition_block(
                sp, ss, h, stage, gate_params, gst, glob, n_blocks, e2, rng,
                train, slu_on)
        nss: Params = {"trans": nbst}
        kps.append(kp[None]); exs.append(ex[None])
        if n > 1:
            globs = jnp.arange(glob + 1, glob + n)

            def body(carry, xs, n_blocks=n_blocks, stage=stage):
                h, gst = carry
                bp, bs, g = xs
                with jax.named_scope(f"cost:s{stage}.rest"):
                    h, nbst, gst, kp, ex = _gated_block(
                        bp, bs, h, gate_params, gst, g, n_blocks, e2, rng,
                        train, slu_on)
                return (h, gst), (nbst, kp, ex)

            (h, gst), (rest_ns, rest_kp, rest_ex) = lax.scan(
                body, (h, gst), (sp["rest"], ss["rest"], globs))
            nss["rest"] = rest_ns
            kps.append(rest_kp); exs.append(rest_ex)
        new_state["stages"].append(nss)

    with jax.named_scope("cost:fc"):
        pooled = jnp.mean(h, axis=(1, 2))
        logits = pooled @ p["fc_w"] + p["fc_b"]
    kps_a = jnp.concatenate(kps)
    aux = {"slu_cost": jnp.mean(kps_a) if slu_on else jnp.float32(1.0),
           "slu_executed": jnp.concatenate(exs), "slu_keep_probs": kps_a}
    return logits, aux, new_state


def resnet_fwd_ref(p: Params, state: Params, x: jnp.ndarray, depth: int,
                   e2: Optional[E2TrainConfig] = None,
                   rng: Optional[jnp.ndarray] = None,
                   train: bool = True
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Params]:
    """Per-block unrolled reference over the same parameter layout.

    Semantics anchor for the scanned forward (identical block math, RNG
    folding, gate-state order, and BN-state threading — only the iteration
    strategy differs).  Kept for parity tests; ResNet-110 through this path
    unrolls 54 blocks and traces accordingly slowly.
    """
    n = resnet_depth_to_n(depth)
    e2 = e2 or E2TrainConfig()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    slu_on = e2.slu.enabled and train and "slu_gate" in p
    gate_params = p.get("slu_gate")
    n_blocks = 3 * n

    h, ns_stem = batchnorm(p["stem_bn"], state["stem_bn"],
                           conv2d(p["stem"], x), train)
    h = jax.nn.relu(h)
    gst = slu.init_gate_state(e2.slu)
    new_state: Params = {"stem_bn": ns_stem, "stages": []}
    kps, exs = [], []
    for stage in range(3):
        sp, ss = p["stages"][stage], state["stages"][stage]
        glob = stage * n
        h, nbst, gst, kp, ex = _transition_block(
            sp, ss, h, stage, gate_params, gst, glob, n_blocks, e2, rng,
            train, slu_on)
        nss: Params = {"trans": nbst}
        kps.append(kp); exs.append(ex)
        if n > 1:
            rest_ns = []
            for b in range(n - 1):
                bp = jax.tree.map(lambda a, b=b: a[b], sp["rest"])
                bs = jax.tree.map(lambda a, b=b: a[b], ss["rest"])
                h, nbst, gst, kp, ex = _gated_block(
                    bp, bs, h, gate_params, gst, jnp.int32(glob + 1 + b),
                    n_blocks, e2, rng, train, slu_on)
                rest_ns.append(nbst)
                kps.append(kp); exs.append(ex)
            nss["rest"] = _stack(rest_ns)
        new_state["stages"].append(nss)

    pooled = jnp.mean(h, axis=(1, 2))
    logits = pooled @ p["fc_w"] + p["fc_b"]
    kps_a = jnp.stack(kps)
    aux = {"slu_cost": jnp.mean(kps_a) if slu_on else jnp.float32(1.0),
           "slu_executed": jnp.stack(exs), "slu_keep_probs": kps_a}
    return logits, aux, new_state


def resnet_loss(p: Params, state: Params, batch, depth: int, e2=None,
                rng=None, train: bool = True, fwd=resnet_fwd):
    """Cross-entropy + SLU FLOPs regularizer (Eq. 1).

    Returns ``(total, (metrics, new_state))`` — the task-registry loss
    contract (``repro.tasks``); ``new_state`` is the updated BN-stat tree.
    """
    e2 = e2 or E2TrainConfig()
    logits, aux, new_state = fwd(p, state, batch["image"], depth, e2, rng,
                                 train=train)
    return _classifier_loss(logits, aux, batch["label"], e2, new_state)


def _classifier_loss(logits, aux, labels, e2: E2TrainConfig, new_state):
    """Cross-entropy + ``alpha`` x the mean keep probability (Eq. 1) and
    the SLU metrics, in the task-registry loss contract."""
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    total = nll + (e2.slu.alpha * aux["slu_cost"] if e2.slu.enabled else 0.0)
    metrics = {"loss": nll, "slu_cost": aux["slu_cost"],
               "slu_exec_ratio": jnp.mean(aux["slu_executed"])}
    return total, (metrics, new_state)


# ---------------------------------------------------------------------------
# MobileNetV2 (CIFAR variant: stride-1 stem and first 24-channel stage)
# ---------------------------------------------------------------------------

MBV2_CFG = [  # (expansion, cout, blocks, stride)
    (1, 16, 1, 1), (6, 24, 2, 1), (6, 32, 3, 2),
    (6, 64, 4, 2), (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def _mbv2_layout() -> List[Tuple[int, int, int, int, bool]]:
    """Static per-block (cin, hidden, cout, stride, residual) — architecture
    facts stay out of the param pytree so optimizers only see arrays."""
    cin, out = 32, []
    for t, c, nblk, s in MBV2_CFG:
        for b in range(nblk):
            stride = s if b == 0 else 1
            out.append((cin, cin * t, c, stride, stride == 1 and cin == c))
            cin = c
    return out


def mbv2_gate_width() -> int:
    """Width of the SLU gate's pooled input: the widest input of an
    identity-shortcut block (narrower inputs are zero-padded to it)."""
    return max(cin for cin, _h, _c, _s, res in _mbv2_layout() if res)


def init_mobilenetv2(key, num_classes: int = 10,
                     e2: Optional[E2TrainConfig] = None
                     ) -> Tuple[Params, Params]:
    """Returns (params, state): state is the BatchNorm running-stat tree.
    With SLU on, ``slu_gate`` is one weight-shared gate over the identity
    blocks' inputs (its key is drawn after every other, so the other
    weights do not depend on SLU)."""
    e2 = e2 or E2TrainConfig()
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    p: Params = {"stem": init_conv(keys[next(ki)], 3, 32), "stem_bn": init_bn(32),
                 "blocks": []}
    s: Params = {"stem_bn": init_bn_state(32), "blocks": []}
    for cin, hidden, c, _stride, _res in _mbv2_layout():
        blk = {"expand": init_conv(keys[next(ki)], cin, hidden, k=1),
               "bn1": init_bn(hidden),
               "dw": dense_init(keys[next(ki)], (3 * 3, hidden), jnp.float32),
               "bn2": init_bn(hidden),
               "project": init_conv(keys[next(ki)], hidden, c, k=1),
               "bn3": init_bn(c)}
        p["blocks"].append(blk)
        s["blocks"].append({"bn1": init_bn_state(hidden),
                            "bn2": init_bn_state(hidden),
                            "bn3": init_bn_state(c)})
    last_cout = _mbv2_layout()[-1][2]
    p["head"] = init_conv(keys[next(ki)], last_cout, 1280, k=1)
    p["head_bn"] = init_bn(1280)
    s["head_bn"] = init_bn_state(1280)
    p["fc_w"] = dense_init(keys[next(ki)], (1280, num_classes), jnp.float32)
    p["fc_b"] = jnp.zeros((num_classes,))
    if e2.slu.enabled:
        p["slu_gate"] = slu.init_gate(keys[next(ki)], mbv2_gate_width(),
                                      e2.slu)
    return p, s


def _depthwise(w: jnp.ndarray, x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """3x3 depthwise conv (``kernels/psg_depthwise.depthwise_conv``: the
    stride applied to the windows before the multiply-sum).  With an
    active PSG context it runs through ``psg.depthwise``: the forward on
    the ``bits_x`` grid, the weight gradient by Eq. (2) in the PSG
    depthwise kernel."""
    if psg.active_config() is not None:
        return psg.depthwise(x, w, stride=stride)
    return depthwise_conv(x, w, stride)


def _mbv2_branch(blk: Params, bst: Params, h: jnp.ndarray, stride: int,
                 train: bool, i: int) -> Tuple[jnp.ndarray, Params]:
    """Inverted-residual branch: 1x1 expand-BN-relu6, 3x3 depthwise-BN-
    relu6, linear 1x1 project-BN; returns (branch, new bn state)."""
    y, ns1 = batchnorm(blk["bn1"], bst["bn1"],
                       conv2d(blk["expand"], h, k=1), train)
    y = jax.nn.relu6(y)
    # nested scopes: the dw tag is innermost, so the audit walker
    # attributes the depthwise multiply-sum separately from the
    # block's 1x1 expand/project convs (their MAC models differ).
    with jax.named_scope(f"cost:b{i}.dw"):
        y = _depthwise(blk["dw"], y, stride)
    y, ns2 = batchnorm(blk["bn2"], bst["bn2"], y, train)
    y = jax.nn.relu6(y)
    y, ns3 = batchnorm(blk["bn3"], bst["bn3"],
                       conv2d(blk["project"], y, k=1), train)
    return y, {"bn1": ns1, "bn2": ns2, "bn3": ns3}


def mobilenetv2_fwd(p: Params, state: Params, x: jnp.ndarray,
                    e2: Optional[E2TrainConfig] = None,
                    rng: Optional[jnp.ndarray] = None,
                    train: bool = True
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Params]:
    """x: (B, 32, 32, 3) -> (logits, aux{slu_*}, new running-stat state).

    The 17 blocks are unrolled (their widths differ).  With SLU on (and
    ``train``), the 10 identity-shortcut blocks are gated as ResNet's are
    (:func:`_slu_skip`: the weight-shared gate, a Bernoulli draw folded
    from the block index, ``never_skip_first_last`` on the first and last
    *gated* block); the 7 blocks without an identity shortcut always run
    and report executed 1.  ``aux`` holds one keep probability and one
    executed flag per block, 17 each.
    """
    e2 = e2 or E2TrainConfig()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    slu_on = e2.slu.enabled and train and "slu_gate" in p
    gate_params = p.get("slu_gate")
    layout = _mbv2_layout()
    gated = [i for i, blk in enumerate(layout) if blk[4]]
    ends = (gated[0], gated[-1]) if e2.slu.never_skip_first_last else ()
    with jax.named_scope("cost:stem"):
        h, ns_stem = batchnorm(p["stem_bn"], state["stem_bn"],
                               conv2d(p["stem"], x), train)
        h = jax.nn.relu6(h)
    gst = slu.init_gate_state(e2.slu)
    new_state: Params = {"stem_bn": ns_stem, "blocks": []}
    kps, exs = [], []
    for i, (blk, bst, (_cin, _hid, _c, stride, residual)) in enumerate(zip(
            p["blocks"], state["blocks"], layout)):
        with jax.named_scope(f"cost:b{i}"):
            if residual and slu_on:
                h, nbst, gst, kp, ex = _slu_skip(
                    lambda h, bst, blk=blk, i=i: _mbv2_branch(
                        blk, bst, h, 1, train, i),
                    h, bst, gate_params, gst, i, jnp.bool_(i in ends), e2,
                    rng)
            else:
                inp = h
                y, nbst = _mbv2_branch(blk, bst, h, stride, train, i)
                h = inp + y if residual else y
                kp = ex = jnp.float32(1.0)
        new_state["blocks"].append(nbst)
        kps.append(kp)
        exs.append(ex)
    with jax.named_scope("cost:head"):
        h, ns_head = batchnorm(p["head_bn"], state["head_bn"],
                               conv2d(p["head"], h, k=1), train)
        h = jax.nn.relu6(h)
    new_state["head_bn"] = ns_head
    with jax.named_scope("cost:fc"):
        pooled = jnp.mean(h, axis=(1, 2))
        logits = pooled @ p["fc_w"] + p["fc_b"]
    kps_a = jnp.stack(kps)
    aux = {"slu_cost": jnp.mean(kps_a) if slu_on else jnp.float32(1.0),
           "slu_executed": jnp.stack(exs), "slu_keep_probs": kps_a}
    return logits, aux, new_state


def mobilenetv2_loss(p: Params, state: Params, batch,
                     e2: Optional[E2TrainConfig] = None, rng=None,
                     train: bool = True):
    """Cross-entropy + SLU FLOPs regularizer (Eq. 1), as
    :func:`resnet_loss`: ``slu_cost`` and ``slu_exec_ratio`` are means over
    all 17 blocks.  Returns ``(total, (metrics, new_state))``."""
    e2 = e2 or E2TrainConfig()
    logits, aux, new_state = mobilenetv2_fwd(p, state, batch["image"], e2,
                                             rng, train=train)
    return _classifier_loss(logits, aux, batch["label"], e2, new_state)
