"""Data-level technique: Stochastic Mini-batch Dropping (SMD), paper §3.1.

At each training step, the mini-batch is skipped with probability
``drop_prob`` (paper default 0.5).  The decision is a *counter-based*
deterministic function of ``(seed, step)`` so that in a multi-pod SPMD
setting every host independently computes the same decision — no collective
is needed to agree on a drop, which is what lets SMD double as straggler
mitigation (DESIGN.md §7): a pod that would miss the step deadline declares
the step dropped, and because SMD-style sampling-with-replacement is exactly
what the training dynamics already tolerate, convergence is unaffected.

The host's decisions (``smd_keep_host``, the data pipeline's thread, the
per-step loop, ``SMDIterator``, ``smd_schedule``) run ``smd_keep`` itself,
vmapped over blocks of consecutive steps on the host's CPU backend and
cached per block, never on the accelerator: there each decision would be
a few tiny device programs queued behind the running chunk program, and
the pipeline would wait on the chip for a scalar.  Threefry and the uniform
conversion are integer and exact-float operations, so the CPU's decisions
are bit for bit the accelerator's.

``equivalent_steps`` maps a full-training iteration budget to the number of
*executed* steps under SMD; the paper's adopted operating point is energy
ratio 0.67 (i.e. SMD with 2x the nominal epochs costs 0.67x the energy but
reaches higher accuracy than the standard protocol, Fig. 3a).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SMDConfig


def smd_keep(seed: int, step, drop_prob: float):
    """Traceable keep-decision for step ``step`` (jnp scalar or int)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.uniform(key) >= drop_prob


# steps per host decision block: a block costs one small CPU program, and a
# chunk of the compiled loop needs about 8 decisions
_BLOCK = 256


@functools.partial(jax.jit, static_argnums=(0, 2))
def _keep_block(seed: int, steps, drop_prob: float):
    """``smd_keep`` over a vector of steps; ``seed`` is static so that
    ``PRNGKey(seed)`` is built exactly as in ``smd_keep`` (seeds >= 2**31
    included)."""
    return jax.vmap(lambda s: smd_keep(seed, s, drop_prob))(steps)


def _decide_block(seed: int, drop_prob: float, block: int) -> jax.Array:
    """Keep decisions of steps ``block*_BLOCK .. (block+1)*_BLOCK - 1``,
    computed on the CPU backend, or on the default device in a process
    that has no CPU backend."""
    try:
        device = jax.devices("cpu")[0]
    except RuntimeError:                    # no CPU backend in this process
        device = None
    steps = np.arange(block * _BLOCK, (block + 1) * _BLOCK, dtype=np.uint32)
    return _keep_block(seed, jax.device_put(steps, device), drop_prob)


@functools.lru_cache(maxsize=64)
def _host_block(seed: int, drop_prob: float, block: int) -> np.ndarray:
    keep = np.array(_decide_block(seed, drop_prob, block))
    keep.flags.writeable = False            # shared by every caller
    return keep


def smd_keep_host(seed: int, step: int, drop_prob: float) -> bool:
    """Host-side (non-traced) version: decides whether to even fetch data.
    Read from a cached block of ``smd_keep``'s decisions on the host CPU."""
    block, i = divmod(int(step), _BLOCK)
    return bool(_host_block(seed, drop_prob, block)[i])


def smd_schedule(cfg: SMDConfig, seed: int, total_steps: int) -> np.ndarray:
    """Boolean keep-mask for a whole run (for logging / tests)."""
    if not cfg.enabled:
        return np.ones((total_steps,), bool)
    return np.array([smd_keep_host(seed, t, cfg.drop_prob)
                     for t in range(total_steps)])


def expected_energy_ratio(cfg: SMDConfig,
                          epochs_multiplier: Optional[float] = None) -> float:
    """Energy of SMD training relative to standard training.

    Running SMD for ``m x`` the nominal iterations costs ``m * (1 - p)``
    of standard training's per-sample compute.  ``m`` defaults to the
    config's declared protocol (``cfg.epochs_multiplier``); the paper's
    operating point (Fig. 3a) is m=4/3, p=0.5 -> 0.67.
    """
    if not cfg.enabled:
        return 1.0 if epochs_multiplier is None else epochs_multiplier
    m = cfg.epochs_multiplier if epochs_multiplier is None else epochs_multiplier
    return m * (1.0 - cfg.drop_prob)


class SMDIterator:
    """Wrap a data iterator; yields (step, batch_or_None).

    ``None`` means the step is dropped — the training loop must skip compute
    *and data fetch* (the underlying iterator is not advanced), which is the
    zero-overhead property the paper relies on.
    """

    def __init__(self, it, cfg: SMDConfig, seed: int, start_step: int = 0):
        self._it = it
        self._cfg = cfg
        self._seed = seed
        self._step = start_step

    def __iter__(self):
        return self

    def __next__(self):
        step = self._step
        self._step += 1
        if self._cfg.enabled and not smd_keep_host(self._seed, step,
                                                   self._cfg.drop_prob):
            return step, None
        return step, next(self._it)
