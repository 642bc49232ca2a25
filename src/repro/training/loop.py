"""Compiled chunked training loop (DESIGN.md §Loop).

The per-step loop dispatches one jitted step per Python iteration and
blocks on a host sync for every metric.  This module compiles K executed
steps into ONE device program (``lax.scan`` over :func:`make_train_step`)
so steady-state training has no per-step Python, no per-step host sync,
and no per-step data fetch for SMD-dropped steps:

* SMD decisions stay **host-side and counter-based** (``smd_schedule``):
  a dropped step never reaches the device, costs no compute and no data
  generation — the paper's §3.1 zero-overhead property.  What the scan
  sees is only the chunk's *executed* steps.
* The step counter still advances **inside** the scan: each executed step
  carries a ``step_increment`` = 1 + the number of drops immediately
  before it, so ``state.step`` (which seeds the per-step RNG fold-in) is
  bit-identical to the per-step loop's.
* Metrics accumulate device-resident and come back stacked ``(K, ...)``;
  the caller syncs them once per chunk boundary.

Trailing drops (after the chunk's last executed step) are NOT part of the
chunk — they belong to the next chunk's first increment, or to
:func:`ChunkPlanner.flush_trailing` at the end of the run.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.config import Experiment
from repro.training.train_step import TrainState, make_train_step

# The chunk program's contract, verified statically per commit by
# ``analysis/hotloop_lint.py`` (DESIGN.md §Analysis).  Rule names are the
# lint's vocabulary — keep them in sync with its rule table:
#
# * no-host-callback        — nothing inside the scan calls back to the
#                             host (debug prints, io_callback, infeed);
#                             one callback per step is the per-step loop's
#                             sync cost all over again.
# * static-trip-count       — the chunk is a ``lax.scan`` with a static K,
#                             never a ``while`` (unknown trips poison the
#                             HLO cost audit and defeat ahead-of-time
#                             scheduling).
# * shape-stable-body       — the scanned body's primitive mix must not
#                             depend on K (a Python-value-dependent
#                             operand would recompile per chunk length).
# * device-resident-metrics — metrics return stacked ``(K, ...)``; the
#                             sync happens at chunk boundaries, in the
#                             caller.
# * no-donation-default     — callers jit WITHOUT ``donate_argnums`` by
#                             default (see the docstring below;
#                             ``Trainer(donate_chunk_state=True)`` is the
#                             explicit opt-in).
#
# The straggler-deadline instrumentation (``make_chunk_step(...,
# step_timer=...)``) is an EXPLICIT OPT-IN that trades one ordered host
# callback per scanned step for per-step wall-clock visibility — the same
# opt-in convention as donation.  The default program (what the lint
# traces) stays callback-free; ``Trainer(deadline_s=...)`` is the only
# caller that requests the timed variant (DESIGN.md §Fault-tolerance).
CHUNK_CONTRACT = (
    "no-host-callback",
    "static-trip-count",
    "shape-stable-body",
    "device-resident-metrics",
    "no-donation-default",
)


def make_chunk_step(exp: Experiment, K: Optional[int] = None,
                    step_timer=None):
    """Build ``(state, batches, step_increment) -> (state, stacked_metrics)``.

    ``batches`` is the chunk's executed-step batches stacked along a new
    leading axis; ``step_increment`` is an int32 ``(k,)`` vector (see module
    doc).  ``K`` is an optional declared chunk length: when given, calls are
    validated against it (the tail chunk of a run may be shorter — jit
    retraces per shape, so pass ``K=None`` to accept any length).

    ``step_timer`` opts into the straggler-deadline instrumentation: a
    host callable ``step_timer(step)`` invoked via an ORDERED
    ``jax.debug.callback`` at the top of every scanned step, so the host
    observes device-side per-step boundaries (the gap between consecutive
    callbacks is one executed step's device time).  The default
    (``None``) program contains no callback — the ``CHUNK_CONTRACT``
    ``no-host-callback`` rule applies to it; the timed variant is the
    explicit opt-in ``Trainer(deadline_s=...)`` requests at per-step
    straggler granularity (DESIGN.md §Fault-tolerance).

    The returned function is pure and jittable; callers jit it once and let
    shape-driven retracing handle tail chunks.  Do NOT jit it with
    ``donate_argnums``: donating the carried TrainState lets XLA CPU
    rewrite the scanned body in place, which changes fusion and breaks the
    bit-for-bit parity with the per-step loop (tests/test_loop.py pins it;
    DESIGN.md §Loop records the measurement).
    """
    train_step = make_train_step(exp)

    def chunk_step(state: TrainState, batches: Dict[str, jnp.ndarray],
                   step_increment: jnp.ndarray
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        k = step_increment.shape[0]
        if K is not None and k != K:
            raise ValueError(f"chunk declared K={K} but got {k} steps")
        lead = {l.shape[0] for l in jax.tree.leaves(batches)}
        if lead != {k}:
            raise ValueError(f"stacked batch leading axes {lead} != k={k}")

        def body(st, xs):
            inc, batch = xs
            # advance over the drops *before* this executed step; train_step
            # itself adds the final +1 — net advance per scan step is `inc`
            st = st._replace(step=st.step + (inc - 1))
            if step_timer is not None:
                # ordered: sequenced with the scan's effects so timestamp
                # arrival order matches device step order
                jax.debug.callback(step_timer, st.step, ordered=True)
            return train_step(st, batch)

        # the named scope marks the contract-bearing scan for the static
        # hot-loop lint (metadata only — fusion and numerics unchanged)
        with jax.named_scope("hotloop:chunk"):
            return jax.lax.scan(body, state,
                                (step_increment.astype(jnp.int32), batches))

    return chunk_step


# host span of one chunk's stacking, on the thread that runs the loop (chunk)
STACK = "trainer.stack"


def stack_batches(batches: Sequence[Dict[str, Any]]):
    """Stack per-step batches into the chunk's leading-K layout.

    Stacks on the HOST (np.stack): the stacked batch then reaches the
    device in ONE transfer — at the trainer's ``device_put`` (sharded
    layout under a mesh) or implicitly at the chunk call.  ``jnp.stack``
    would commit the stack to the default device first and mesh placement
    would pay a second full copy to reshard it.
    """
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *batches)


class ChunkPlanner:
    """Groups a stream of ``(step, batch_or_None)`` items into chunks.

    Feed items in nominal-step order (``DataPipeline`` / ``SMDIterator``
    yield exactly this); ``None`` means the step was SMD-dropped before
    generation.  ``add`` returns a completed ``(steps, batches,
    increments)`` chunk once ``chunk_steps`` executed steps accumulated,
    else ``None``.  ``flush`` returns the final partial chunk;
    ``flush_trailing`` returns drops after the last executed step (the
    caller advances the device step counter by that much once, at the end).
    ``chunks`` counts the chunks emitted; each is stacked inside a
    :data:`STACK` host span carrying its index.
    """

    def __init__(self, chunk_steps: int):
        self.chunk_steps = chunk_steps
        self._steps: List[int] = []
        self._batches: List[Any] = []
        self._incs: List[int] = []
        self._pending_drops = 0
        self.dropped = 0
        self.executed = 0
        self.chunks = 0

    def add(self, step: int, batch):
        if batch is None:
            self._pending_drops += 1
            self.dropped += 1
            return None
        self._steps.append(step)
        self._batches.append(batch)
        self._incs.append(self._pending_drops + 1)
        self._pending_drops = 0
        self.executed += 1
        if len(self._steps) == self.chunk_steps:
            return self._emit()
        return None

    def drop(self, step: int, batch) -> None:
        """Force-drop a kept step (straggler policy): the generated batch is
        discarded and the step is accounted exactly like an SMD drop."""
        del step, batch
        self._pending_drops += 1
        self.dropped += 1

    def flush(self):
        """The final partial chunk, or ``None`` if no executed step is
        buffered (trailing drops stay pending for ``flush_trailing``)."""
        if not self._steps:
            return None
        return self._emit()

    def flush_trailing(self) -> int:
        n, self._pending_drops = self._pending_drops, 0
        return n

    def _emit(self):
        steps = tuple(self._steps)
        with TraceAnnotation(STACK, chunk=self.chunks):
            batches = stack_batches(self._batches)
        self.chunks += 1
        incs = np.asarray(self._incs, np.int32)
        self._steps, self._batches, self._incs = [], [], []
        return steps, batches, incs
