"""Train-step builder: loss + grad + E²-Train integration + optimizer.

One function, ``make_train_step(exp)``, returns a pure jittable
``(state, batch) -> (state, metrics)`` covering:

* the experiment's task (``repro.tasks`` registry: LM or CIFAR CNN — the
  step builder is model-agnostic),
* mixed-precision loss (params fp32, activations per model config),
* PSG routing (trace-time ``psg.enable``) and sign-gradient handling,
* microbatch gradient accumulation (``lax.scan``; for PSG the per-micro
  signs sum then re-sign — a majority vote over microbatches),
* majority-vote 1-bit compression marker (sign() after pjit's mean-reduce),
* SLU rng/regularizer plumbing (inside the model),
* non-trainable model state (BatchNorm running stats) threaded past the
  optimizer: the loss returns the updated buffers, the step stores them on
  ``TrainState.model_state`` — they are never touched by the optimizer,
* optimizer + optional SWA.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import psg as psgmod
from repro.core.config import Experiment
from repro.distributed.sharding import constrain_like_params
from repro.optim.api import make_optimizer
from repro.optim.majority_vote import majority_vote_tree
from repro.optim.swa import swa_init, swa_params, swa_update
from repro.tasks import get_task


class TrainState(NamedTuple):
    params: Any
    opt: Any
    swa: Any                     # None when disabled (static)
    step: jnp.ndarray
    model_state: Any = None      # non-trainable buffers (BN running stats)


def init_train_state(key, exp: Experiment) -> TrainState:
    task = get_task(exp.task)
    params, model_state = task.init(key, exp)
    opt = make_optimizer(exp.train)
    swa = swa_init(params) if (exp.e2.psg.enabled and exp.e2.psg.swa) else None
    return TrainState(params=params, opt=opt.init(params), swa=swa,
                      step=jnp.zeros((), jnp.int32), model_state=model_state)


def make_train_step(exp: Experiment):
    e2, tc = exp.e2, exp.train
    task = get_task(exp.task)
    task_loss = task.make_loss(exp)
    opt = make_optimizer(tc)
    psg_cfg = e2.psg if e2.psg.enabled else None
    dw_sites = task.depthwise is not None and task.depthwise(exp)
    m = max(tc.microbatches, 1)

    def loss_fn(params, model_state, probe, batch, rng):
        # probe: zero_probe() carrier — its gradient accumulates the PSG
        # kernels' fallback statistics across the whole backward pass
        # (core/psg.py), giving the measured per-step psg_fallback_ratio
        # (and psg_dw_fallback_ratio) without a side channel.
        with psgmod.enable(psg_cfg, probe=probe):
            return task_loss(params, model_state, batch, rng)

    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 2), has_aux=True)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        rng = jax.random.fold_in(jax.random.PRNGKey(tc.seed), state.step)
        probe0 = psgmod.zero_probe()
        if m == 1:
            (loss, (metrics, mstate)), (grads, probe_g) = grad_fn(
                state.params, state.model_state, probe0, batch, rng)
            grads = constrain_like_params(grads)
        else:
            def micro(carry, mb):
                g_acc, p_acc, ms, i = carry
                (l, (mt, ms2)), (g, pg) = grad_fn(
                    state.params, ms, probe0, mb, jax.random.fold_in(rng, i))
                g = constrain_like_params(g)
                acc = constrain_like_params(jax.tree.map(jnp.add, g_acc, g))
                return (acc, p_acc + pg, ms2, i + 1), (l, mt)

            mbs = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch)
            g0 = jax.tree.map(jnp.zeros_like, state.params)
            (grads, probe_g, mstate, _), (losses, mets) = jax.lax.scan(
                micro, (g0, probe0, state.model_state, 0), mbs)
            grads = jax.tree.map(lambda g: g / m, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, mets)

        if psg_cfg is not None:
            # per-replica signs were mean-reduced by pjit across data/pod;
            # the final sign() completes the distributed majority vote.
            grads = majority_vote_tree(grads)
        if tc.grad_clip > 0 and psg_cfg is None:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, tc.grad_clip / (gn + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        else:
            gn = jnp.float32(0.0)

        params, opt_state = opt.apply(state.params, grads, state.opt,
                                      state.step)
        swa = state.swa
        if swa is not None:
            swa = swa_update(swa, params, state.step,
                             int(tc.total_steps * e2.psg.swa_start_frac))
        metrics = dict(metrics)
        metrics["total_loss"] = loss
        metrics["grad_norm"] = gn
        if psg_cfg is not None:
            # measured (not assumed) predictor usage: MAC-weighted fraction
            # of backward kernel tiles that ran the full-precision fallback
            # product.  Only emitted when PSG ran — a baseline step has no
            # measurement, not a measurement of zero.
            metrics["psg_fallback_ratio"] = psgmod.probe_fallback_ratio(probe_g)
            if dw_sites:
                # share of depthwise weight-gradient entries whose sign
                # the full product decided (models with depthwise convs)
                metrics["psg_dw_fallback_ratio"] = \
                    psgmod.probe_dw_fallback_ratio(probe_g)
        return TrainState(params, opt_state, swa, state.step + 1,
                          mstate), metrics

    return train_step


def eval_params(state: TrainState, exp: Experiment):
    """Weights to evaluate with — SWA average when PSG+SWA is active.

    Caveat for tasks with non-trainable buffers (BN running stats): the
    stats in ``state.model_state`` tracked the *raw* parameter trajectory,
    not the SWA average — evaluate SWA weights with
    :func:`recalibrate_model_state` output, per standard SWA practice.
    """
    if state.swa is not None:
        return swa_params(state.swa, state.params)
    return state.params


def recalibrate_model_state(exp: Experiment, params, model_state, batches,
                            rng=None):
    """Re-estimate non-trainable buffers under ``params`` by running
    train-mode forwards over ``batches`` (SWA BN-recalibration).  A no-op
    for stateless tasks (the LM): the input state passes through."""
    if not jax.tree.leaves(model_state):
        return model_state
    loss = get_task(exp.task).make_loss(exp)
    rng = rng if rng is not None else jax.random.PRNGKey(exp.train.seed)
    for i, batch in enumerate(batches):
        _, (_, model_state) = loss(params, model_state, batch,
                                   jax.random.fold_in(rng, i))
    return model_state
