"""Host-side data pipeline: per-pod sharding, background prefetch, SMD.

At scale each host generates/loads only its shard of the global batch (the
synthetic generators are counter-based so shards never overlap).  A small
background thread keeps ``prefetch`` batches ready; SMD drops are decided
*before* generation, on the host's CPU (``core/smd.smd_keep_host``), so a
dropped step costs nothing — the zero-overhead property the paper's
data-level technique relies on.

The pipeline keeps the seconds and the count of the SMD decisions behind
the items it has handed out (``smd_decide_s``, ``smd_decisions``), and
marks each decision and each generated batch with a host span
(:data:`SMD_DECIDE`, :data:`MAKE_BATCH`; the trainer lists every span in
``training/trainer.SPANS``).
"""
from __future__ import annotations

import ctypes
import queue
import sys
import threading
import time
from typing import Callable, Dict, Iterator, Optional

from jax.profiler import TraceAnnotation

from repro.core.config import SMDConfig
from repro.core.smd import smd_keep_host

# host spans of the pipeline thread, one per nominal step (step)
SMD_DECIDE = "pipeline.smd_decide"      # one SMD keep decision
MAKE_BATCH = "pipeline.make_batch"      # one kept step's batch
THREAD_NAME = b"repro-pipeline"


class DataPipeline:
    def __init__(self, make_batch: Callable[[int, int], Dict],
                 smd: Optional[SMDConfig] = None,
                 seed: int = 0, shard: int = 0,
                 prefetch: int = 2, start_step: int = 0):
        """make_batch(step, shard) -> batch dict."""
        self._make = make_batch
        self._smd = smd or SMDConfig()
        self._seed = seed
        self._shard = shard
        self._step = start_step
        self._q: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
        self._stop = threading.Event()
        # a make_batch exception must not die with the producer thread: it
        # is captured here and re-raised in the CONSUMER (__next__), so the
        # trainer sees it within one get-timeout instead of spinning on an
        # empty queue forever (the pre-PR 10 hang)
        self._error: Optional[BaseException] = None
        self.smd_decide_s = 0.0       # over the items handed out
        self.smd_decisions = 0
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def _producer(self):
        _name_thread(THREAD_NAME)
        step = self._step
        while not self._stop.is_set():
            try:
                keep, batch, decide_s = True, None, None
                if self._smd.enabled:
                    with TraceAnnotation(SMD_DECIDE, step=step):
                        t = time.perf_counter()
                        keep = smd_keep_host(self._seed, step,
                                             self._smd.drop_prob)
                        decide_s = time.perf_counter() - t
                if keep:                # a dropped step is never generated
                    with TraceAnnotation(MAKE_BATCH, step=step):
                        batch = self._make(step, self._shard)
                item = (step, batch, decide_s)
            except BaseException as e:              # surfaced, never swallowed
                self._error = e
                return
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        while True:
            if self._stop.is_set():
                raise StopIteration
            try:
                step, batch, decide_s = self._q.get(timeout=0.1)
            except queue.Empty:
                if self._error is not None:
                    # producer died on this exception; queue is drained by
                    # now, so every already-generated batch was consumed —
                    # re-raise the ORIGINAL exception at the call site
                    self._stop.set()
                    raise self._error
                continue
            if decide_s is not None:
                self.smd_decide_s += decide_s
                self.smd_decisions += 1
            return step, batch                      # batch None: SMD drop

    def close(self, timeout: float = 5.0) -> bool:
        """Stop the producer and join it.

        Draining the queue once is not enough: the producer may be parked in
        ``put`` with a ready item and complete the put right after the
        drain, then go generate the next batch — a shutdown race that leaves
        the thread alive holding references.  So: signal stop, then
        alternate drain + short join until the thread exits (it re-checks
        the stop flag at least every 0.1 s put timeout).  Returns whether
        the producer actually terminated within ``timeout``.
        """
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive():
            self._drain()
            self._thread.join(timeout=0.05)
            if time.monotonic() > deadline:
                break
        self._drain()                    # a post-join straggler put
        return not self._thread.is_alive()

    def _drain(self):
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def _name_thread(name: bytes) -> None:
    """Name the calling thread for the OS (Linux), where profilers read
    it.  Python names its threads only inside the interpreter, so they all
    carry the process's name and a profile's host lines look alike."""
    if sys.platform.startswith("linux"):
        try:
            ctypes.CDLL(None).prctl(15, name[:15], 0, 0, 0)  # PR_SET_NAME
        except (OSError, AttributeError):
            pass
