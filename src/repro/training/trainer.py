"""Training loop orchestration: SMD, checkpoints, straggler policy, metrics.

All compute lives in jitted device programs and everything model-specific
lives behind the ``repro.tasks`` registry, so the same loop trains the
transformer LM stack and the paper's CIFAR CNNs (there is no other
training loop in the repo).  Two execution modes share one Trainer
(DESIGN.md §Loop):

* **per-step** (``chunk_steps=1``, no mesh): one jitted train_step per
  Python iteration, metrics synced every step — the reference loop the
  chunked mode is parity-tested against;
* **chunked** (``chunk_steps=K>1`` or ``mesh=...``): K executed steps
  compile into one ``lax.scan`` program (``training/loop.py``); batches
  come from ``data/pipeline.py``'s background prefetch thread and are
  ``jax.device_put`` while the previous chunk still runs; metrics stay
  device-resident and sync once per chunk boundary.  With ``mesh=...``
  the stacked batch is sharded along its batch axis
  (``distributed/sharding.batch_sharding``) and the TrainState is
  replicated/FSDP-sharded (``state_shardings``) — data-parallel execution
  with counter-based per-shard batch generation, no host data exchange.

Operational concerns of a long-running multi-pod job, in both modes:

* SMD-dropped steps advance the step counter without compute or data fetch
  (decided host-side from the counter-based schedule; in chunked mode the
  drops never even reach the device — they ride along as per-executed-step
  ``step_increment`` values);
* periodic + final checkpoints via ``repro.ft.checkpoint`` (async save);
  in chunked mode the cadence is evaluated at chunk granularity and saves
  land on chunk boundaries (``repro.ft.checkpoint.resume_chunk_start``);
* a straggler hook at PER-STEP granularity in both modes: per-step mode
  times each dispatch directly; chunked mode opts into the timed chunk
  program (``make_chunk_step(step_timer=...)`` — one ordered host
  callback per scanned step, so per-step device-side boundaries are
  observable without breaking the chunk into per-step dispatches).  Every
  step whose wall time exceeds ``deadline_s`` arms one forced drop; armed
  drops are consumed by subsequent kept steps (``ChunkPlanner.drop``) and
  counted in ``straggler_dropped_steps``, which ``energy_report()``
  surfaces — the SMD machinery makes forced drops sound (DESIGN.md
  §Fault-tolerance).  On real multi-host deployments the deadline check
  runs per-host against the shared counter-based SMD schedule.

Host spans (``jax.profiler.TraceAnnotation``, named in :data:`SPANS`) mark
where the chunked loop and its data pipeline spend host time, so a
profile labels each idle stretch of the device by the host work it
waited on.  They cost one check per span when no profile is taken.
"""
from __future__ import annotations

import contextlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.config import Experiment
from repro.core.smd import smd_keep_host
from repro.data.pipeline import MAKE_BATCH, SMD_DECIDE, DataPipeline
from repro.training.loop import STACK, ChunkPlanner, make_chunk_step
from repro.training.train_step import TrainState, make_train_step

# Host spans of the chunked loop, main thread: one per phase per chunk, one
# after another, so a profile labels an idle gap by the phase that covers
# it (chunk: its index within the run).  Args are host ints already known.
COLLECT = "trainer.collect"       # pull one chunk's items, queue waits
                                  # included (chunk, first_step, last_step)
DISPATCH = "trainer.dispatch"     # place + the chunk program call (chunk)
SYNC = "trainer.sync"             # _finalize: device_get + history (chunk)
CHECKPOINT = "trainer.checkpoint"  # a cadence save (chunk)
# every host span, with STACK (stack_batches, opened by the ChunkPlanner
# inside collect) and the pipeline thread's SMD_DECIDE and MAKE_BATCH (one
# per nominal step, step)
SPANS = (COLLECT, STACK, DISPATCH, SYNC, CHECKPOINT, SMD_DECIDE, MAKE_BATCH)


class Trainer:
    def __init__(self, exp: Experiment, state: TrainState,
                 make_batch: Callable[[int, int], Dict],
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 deadline_s: float = 0.0,
                 shard: int = 0,
                 chunk_steps: int = 1,
                 mesh: Optional[Any] = None,
                 prefetch: int = 2,
                 donate_chunk_state: bool = False):
        self.exp = exp
        self.make_batch = make_batch
        self.step_fn = jax.jit(make_train_step(exp), donate_argnums=(0,))
        self.ckpt_dir = checkpoint_dir
        self.ckpt_every = checkpoint_every
        self.deadline_s = deadline_s
        self.shard = shard
        self.chunk_steps = max(int(chunk_steps), 1)
        self.mesh = mesh
        self.prefetch = prefetch
        self.donate_chunk_state = donate_chunk_state
        self.history: List[Dict[str, float]] = []
        self._straggler_pending = 0     # armed forced drops (a count)
        self._last_sync_t = 0.0
        self.executed_steps = 0
        self.dropped_steps = 0
        self.straggler_dropped_steps = 0   # subset of dropped_steps
        self.save_errors: Dict[str, BaseException] = {}
        self._chunk_fn = None           # built lazily (chunked mode only)
        self._step_times: Dict[int, float] = {}   # timed-chunk timestamps
        if mesh is not None:
            from repro.distributed.sharding import state_shardings
            state = jax.device_put(state, state_shardings(state, mesh))
        self.state = state

    # ------------------------------------------------------------------
    # entry point
    # ------------------------------------------------------------------

    def run(self, num_steps: int, log_every: int = 0) -> List[Dict[str, float]]:
        if self.chunk_steps > 1 or self.mesh is not None:
            return self._run_chunked(num_steps, log_every)
        return self._run_per_step(num_steps, log_every)

    # ------------------------------------------------------------------
    # per-step reference loop (chunk_steps=1): one dispatch + one metrics
    # sync per executed step
    # ------------------------------------------------------------------

    def _run_per_step(self, num_steps: int,
                      log_every: int = 0) -> List[Dict[str, float]]:
        e2 = self.exp.e2
        for _ in range(num_steps):
            step = int(self.state.step)
            drop = False
            if e2.smd.enabled and not smd_keep_host(self.exp.train.seed, step,
                                                    e2.smd.drop_prob):
                drop = True
            forced = False
            if self._straggler_pending:       # straggler -> SMD-style drop
                if not drop:
                    forced = True             # an otherwise-kept step
                drop = True                   # (an SMD drop absorbs the arm)
                self._straggler_pending -= 1
            if drop:
                self.state = self.state._replace(step=self.state.step + 1)
                self.dropped_steps += 1
                self.straggler_dropped_steps += int(forced)
                continue

            batch = self.make_batch(step, self.shard)
            t0 = time.perf_counter()
            self.state, metrics = self.step_fn(self.state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.perf_counter() - t0
            metrics["step"] = step
            metrics["wall_s"] = dt
            self.history.append(metrics)
            self.executed_steps += 1
            if self.deadline_s and dt > self.deadline_s:
                self._straggler_pending += 1
            if self.ckpt_dir and self.ckpt_every and \
                    (step + 1) % self.ckpt_every == 0:
                self._save(step)
            if log_every and step % log_every == 0:
                print(f"step {step}: loss={metrics.get('total_loss', 0):.4f} "
                      f"({dt*1e3:.0f} ms)")
        self._final_save()
        return self.history

    # ------------------------------------------------------------------
    # chunked loop: K executed steps per device program, prefetched data,
    # chunk-boundary metric syncs, optional mesh data-parallelism
    # ------------------------------------------------------------------

    def _run_chunked(self, num_steps: int,
                     log_every: int = 0) -> List[Dict[str, float]]:
        planner = ChunkPlanner(self.chunk_steps)
        self._last_sync_t = 0.0
        start = int(self.state.step)
        pipe = DataPipeline(self.make_batch, self.exp.e2.smd,
                            seed=self.exp.train.seed, shard=self.shard,
                            prefetch=self.prefetch, start_step=start)
        # one-chunk pipeline: while chunk N runs on device, chunk N+1 is
        # assembled from the prefetch queue and device_put (double-buffer);
        # chunk N's metrics sync when N+1 has been dispatched
        in_flight = None          # (chunk, steps, t0, device metrics, smd)
        remaining = num_steps
        try:
            while remaining:
                first = start + planner.executed + planner.dropped
                smd0 = (pipe.smd_decide_s, pipe.smd_decisions)
                with TraceAnnotation(COLLECT, chunk=planner.chunks,
                                     first_step=first) as ann:
                    chunk = self._collect(pipe, planner, start, remaining)
                    consumed = (start + planner.executed + planner.dropped
                                - first)
                    ann.set_metadata(last_step=first + consumed - 1)
                remaining -= consumed
                smd = [pipe.smd_decide_s - smd0[0],
                       pipe.smd_decisions - smd0[1]]
                if chunk is not None:
                    in_flight = self._dispatch(chunk, planner.chunks - 1,
                                               in_flight, smd, log_every)
                elif in_flight is not None:
                    # drops after the run's last executed step: counted
                    # with the last chunk
                    in_flight[4][0] += smd[0]
                    in_flight[4][1] += smd[1]
            if in_flight is not None:
                self._finalize(in_flight, log_every)
        finally:
            pipe.close()
            # keep telemetry consistent even if interrupted mid-run (the
            # per-step loop updates these incrementally): an
            # EnergyLedger.from_trainer after a KeyboardInterrupt must see
            # the counts that produced self.history
            self.executed_steps += planner.executed
            self.dropped_steps += planner.dropped
        trailing = planner.flush_trailing()
        if trailing:
            self.state = self.state._replace(step=self.state.step + trailing)
        self._final_save()
        return self.history

    def chunk_program(self):
        """The jitted chunk program the chunked loop dispatches, built on
        first use: ``(state, batches, incs) -> (state, stacked metrics)``
        on arguments placed by :meth:`place`."""
        if self._chunk_fn is None:
            # donate_chunk_state=False (default): donating the carried
            # TrainState lets XLA CPU rewrite the scanned body in place,
            # which changes fusion and breaks the bit-for-bit parity with
            # the per-step loop that tests/test_loop.py pins (measured:
            # losses drift in the 4th decimal from the second in-chunk step
            # onward; DESIGN.md §Loop).  The cost is one extra TrainState
            # copy per chunk.  Opt in per backend/profile with
            # Trainer(donate_chunk_state=True) — the curve then matches
            # the per-step loop to fp tolerance, not bit-for-bit
            # (tests/test_loop.py::test_donate_chunk_state_parity).
            donate = (0,) if self.donate_chunk_state else ()
            # deadline_s > 0 opts into the TIMED chunk program: one ordered
            # host callback per scanned step records device-side step
            # boundaries, so the straggler deadline applies per step, not
            # per chunk mean (DESIGN.md §Fault-tolerance).  The default
            # program stays callback-free (CHUNK_CONTRACT).  Ordered
            # effects are single-device only in XLA, so mesh runs keep
            # the chunk-mean fallback clock.
            timer = (self._record_step_time
                     if self.deadline_s and self.mesh is None else None)
            self._chunk_fn = jax.jit(
                make_chunk_step(self.exp, step_timer=timer),
                donate_argnums=donate)
        return self._chunk_fn

    def _collect(self, pipe, planner, start, remaining):
        """Pull items until the planner emits a chunk or ``remaining``
        nominal steps are consumed; returns the chunk (the run's partial
        tail chunk in the latter case) or ``None``."""
        for _ in range(remaining):
            step, batch = next(pipe)
            assert step == start + planner.executed + planner.dropped, \
                "pipeline out of lockstep with the SMD schedule"
            if self._straggler_pending:
                # same contract as the per-step loop: each armed drop is
                # consumed by the NEXT step whatever it is — an SMD
                # drop absorbs it (one drop, not two); a kept step is
                # force-dropped (its prefetched batch is discarded)
                self._straggler_pending -= 1
                if batch is not None:
                    planner.drop(step, batch)
                    self.straggler_dropped_steps += 1
                    continue
            chunk = planner.add(step, batch)
            if chunk is not None:
                return chunk
        return planner.flush()

    def _dispatch(self, chunk, ident, in_flight, smd, log_every):
        """device_put + launch chunk number ``ident``; sync the previous
        one after.  ``smd``: [seconds, count] of the SMD decisions the
        chunk consumed."""
        steps, batches, incs = chunk
        with TraceAnnotation(DISPATCH, chunk=ident):
            batches, incs = self.place(batches, incs)
            with self._mesh_ctx():
                t0 = time.perf_counter()
                self.state, stacked = self.chunk_program()(self.state,
                                                           batches, incs)
        if in_flight is not None:
            self._finalize(in_flight, log_every)
        if self.ckpt_dir and self.ckpt_every and any(
                (s + 1) % self.ckpt_every == 0 for s in steps):
            # cadence at chunk granularity: the save waits for THIS chunk
            # (np.asarray blocks) and lands on its boundary — its last
            # executed step — which is what resume derives the
            # chunk-aligned restart from (ft/checkpoint.resume_chunk_start)
            with TraceAnnotation(CHECKPOINT, chunk=ident):
                self._save(steps[-1])
        return ident, steps, t0, stacked, smd

    def place(self, batches, incs):
        """Put one stacked chunk where the chunk program reads it: the
        default device, or under a mesh the batch axis (axis 1) split over
        the data axes and the increments replicated."""
        incs = jnp.asarray(incs)
        if self.mesh is None:
            return batches, incs
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import batch_sharding
        shardings = batch_sharding(self.mesh, batches, batch_axis=1)
        batches = jax.device_put(batches, shardings)
        incs = jax.device_put(incs, NamedSharding(self.mesh, P(None)))
        return batches, incs

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.sharding import activation_sharding
        stack = contextlib.ExitStack()
        stack.enter_context(activation_sharding(self.mesh))
        stack.enter_context(self.mesh)
        return stack

    def _finalize(self, in_flight, log_every):
        """Chunk boundary: ONE host sync for the whole chunk's stacked
        metrics, then bookkeeping at chunk granularity."""
        ident, steps, t0, stacked, smd = in_flight
        with TraceAnnotation(SYNC, chunk=ident):
            host = jax.device_get(stacked)        # blocks until chunk done
            sync_t = time.perf_counter()
            # this chunk was dispatched (t0) while the PREVIOUS one was
            # still running — clamp to the previous sync so overlapped time
            # is not double-counted (else summed wall_s overstates wall
            # clock ~2x and the straggler deadline trips on healthy chunks)
            dt = sync_t - max(t0, self._last_sync_t)
            self._last_sync_t = sync_t
            per_step_s = dt / len(steps)
            for i, step in enumerate(steps):
                metrics = {k: float(v[i]) for k, v in host.items()}
                metrics["step"] = step
                metrics["wall_s"] = per_step_s
                # the SMD decisions the chunk consumed, spread like wall_s
                metrics["smd_decide_s"] = smd[0] / len(steps)
                metrics["smd_decisions"] = smd[1] / len(steps)
                self.history.append(metrics)
                if log_every and step % log_every == 0:
                    print(f"step {step}: "
                          f"loss={metrics.get('total_loss', 0):.4f} "
                          f"({per_step_s*1e3:.0f} ms)")
            if self.deadline_s and not self._arm_stragglers(steps, sync_t):
                # no device-side timestamps arrived (callback not yet
                # flushed or instrumentation unavailable): fall back to the
                # chunk-mean check so a straggling chunk still arms one drop
                if per_step_s > self.deadline_s:
                    self._straggler_pending += 1

    def _record_step_time(self, step) -> None:
        """Ordered-callback target: one timestamp per scanned step, keyed by
        the nominal step counter (runs on JAX's callback thread)."""
        self._step_times[int(step)] = time.perf_counter()

    def _arm_stragglers(self, steps, end_t: float) -> bool:
        """Per-step deadline check over one finished chunk's device-side
        step boundaries.  The gap between consecutive step timestamps is
        one executed step's device time; the chunk's last step is bounded
        by the metrics-sync time (a slight over-estimate — host get
        latency — conservative in the drop direction).  Each straggling
        step arms ONE forced drop.  Returns whether any timestamps were
        available for this chunk."""
        jax.effects_barrier()          # flush this chunk's ordered callbacks
        ts = [self._step_times.pop(s, None) for s in steps]
        if all(t is None for t in ts):
            return False
        for i, t in enumerate(ts):
            if t is None:
                continue
            nxt = next((u for u in ts[i + 1:] if u is not None), end_t)
            if nxt - t > self.deadline_s:
                self._straggler_pending += 1
        return True

    def _final_save(self) -> bool:
        """Final checkpoint; returns whether every pending save landed.

        A failed write (disk full, permission — surfaced by the async
        writer after retries) is REPORTED, never claimed as success: the
        failures land in ``self.save_errors`` and are printed, and the
        caller can decide whether a run without a final checkpoint is
        acceptable.  Training results (history/telemetry) are preserved
        either way."""
        if not self.ckpt_dir:
            return True
        self._save(int(self.state.step) - 1)
        # the final save must survive process exit: async writers are
        # daemon threads, and an orphaned write leaves a stale .tmp
        # (and no checkpoint) for the next --resume to trip over
        from repro.ft.checkpoint import wait_for_saves
        failures = wait_for_saves(raise_on_error=False)
        if failures:
            self.save_errors.update(failures)
            for path, err in failures.items():
                print(f"CHECKPOINT SAVE FAILED (post-retry): {path}: {err!r}",
                      file=sys.stderr)
            return False
        return True

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def steps_per_s(self) -> Optional[float]:
        """Executed-step throughput over the run's measured wall time."""
        wall = sum(h.get("wall_s", 0.0) for h in self.history)
        if not self.history or wall <= 0:
            return None
        return len(self.history) / wall

    def measured_psg_fallback(self) -> Optional[float]:
        """Mean measured PSG fallback-tile ratio over executed steps — the
        quantity core/energy.py uses in place of its 0.4 design assumption
        (``training_energy_pj(psg_fallback_rate=...)``).  ``None`` when no
        PSG step executed: no measurement is not a measurement of zero."""
        vals = [h["psg_fallback_ratio"] for h in self.history
                if "psg_fallback_ratio" in h]
        return float(np.mean(vals)) if vals else None

    def energy_report(self, steps: Optional[int] = None,
                      validate_against_hlo: bool = False):
        """The run's :class:`~repro.core.ledger.EnergyReport`: this run's
        telemetry (SMD executed/dropped counts, SLU execution ratios, PSG
        fallback-tile ratios) composed with the experiment's per-layer cost
        model and the 45nm per-op tables — measured next to assumed
        (DESIGN.md §Energy).  ``steps`` defaults to the config's nominal
        ``total_steps`` budget; ``validate_against_hlo`` additionally runs
        the static cost audit (``analysis/audit.py``, cached per config)
        and stamps its verdict into ``validated_against_hlo``."""
        from repro.core.ledger import EnergyLedger
        return EnergyLedger.from_trainer(self).report(
            steps=steps, validate_against_hlo=validate_against_hlo)

    def _save(self, step: int):
        from repro.ft.checkpoint import save_checkpoint
        save_checkpoint(self.ckpt_dir, self.state, step, async_save=True)
