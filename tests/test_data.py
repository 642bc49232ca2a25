"""Data pipeline: determinism, shard disjointness, learnable structure."""
import time

import numpy as np
import pytest

from repro.core.config import SMDConfig
from repro.core.smd import SMDIterator, smd_schedule
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import (GaussianImageTask, MarkovLMTask,
                                  make_image_batch, make_lm_batch)


def test_lm_batch_deterministic():
    task = MarkovLMTask(vocab=64)
    a = make_lm_batch(task, 0, 3, 0, 4, 16)
    b = make_lm_batch(task, 0, 3, 0, 4, 16)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))


def test_lm_batch_shards_distinct():
    task = MarkovLMTask(vocab=64)
    a = make_lm_batch(task, 0, 3, 0, 4, 16)
    b = make_lm_batch(task, 0, 3, 1, 4, 16)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))


def test_markov_structure_learnable():
    """Labels follow the permutation with prob ~peak."""
    task = MarkovLMTask(vocab=64, peak=0.9)
    batch = make_lm_batch(task, 0, 0, 0, 32, 64)
    toks = np.asarray(batch["tokens"])
    labs = np.asarray(batch["labels"])
    perm = task.transition()
    valid = labs >= 0
    agree = (perm[toks[valid]] == labs[valid]).mean()
    assert 0.85 < agree <= 1.0


def test_image_batch_class_separation():
    task = GaussianImageTask(num_classes=4, snr=3.0)
    b = make_image_batch(task, 0, 0, 0, 64)
    imgs, labs = np.asarray(b["image"]), np.asarray(b["label"])
    means = task.means()
    # nearest-mean classification should beat chance easily at snr 3
    d = ((imgs[:, None] - 3.0 * means[None]) ** 2).sum((2, 3, 4))
    acc = (d.argmin(1) == labs).mean()
    assert acc > 0.9


def test_pipeline_prefetch_and_smd():
    task = MarkovLMTask(vocab=32)
    made = []

    def mk(step, shard):
        made.append(step)
        return make_lm_batch(task, 0, step, shard, 2, 8)

    pipe = DataPipeline(mk, SMDConfig(enabled=True, drop_prob=0.5), seed=0)
    out = [next(pipe) for _ in range(40)]
    pipe.close()
    dropped = [s for s, b in out if b is None]
    kept = [s for s, b in out if b is not None]
    assert len(dropped) + len(kept) == 40
    assert len(dropped) > 5
    assert set(made).isdisjoint(set(dropped))  # dropped never generated


def test_pipeline_close_joins_producer():
    """Shutdown race (pinned): the producer can complete a ``put`` right
    after close() drains the queue and go on generating; close() must
    actually JOIN the thread, not just drain once."""
    mk = lambda step, shard: {"x": np.full((2,), step)}
    pipe = DataPipeline(mk, None, prefetch=1)
    time.sleep(0.3)               # producer fills the queue and parks in put
    assert pipe._thread.is_alive()
    assert pipe.close() is True   # terminated within the timeout
    assert not pipe._thread.is_alive()
    with pytest.raises(StopIteration):
        next(pipe)                # closed pipeline never blocks forever


def test_pipeline_close_mid_consumption():
    """close() while the consumer raced items off the queue still joins."""
    mk = lambda step, shard: {"x": np.full((4,), step)}
    pipe = DataPipeline(mk, None, prefetch=2)
    for _ in range(5):
        next(pipe)
    assert pipe.close() is True
    assert not pipe._thread.is_alive()


def test_pipeline_producer_exception_propagates():
    """Regression (PR 10): a make_batch exception must not die silently
    with the producer thread.  Already-generated batches are consumed
    first, then the ORIGINAL exception re-raises at the consumer call site
    within one get-timeout — instead of the consumer spinning forever on
    an empty queue."""
    def mk(step, shard):
        if step >= 3:
            raise RuntimeError("boom at step 3")
        return {"x": np.full((2,), step)}

    pipe = DataPipeline(mk, None, prefetch=2)
    got = []
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="boom at step 3"):
        for _ in range(10):
            got.append(next(pipe))
    assert time.monotonic() - t0 < 5.0          # surfaced, not a hang
    assert [s for s, _ in got] == [0, 1, 2]     # good batches drained first
    assert pipe.close() is True


def test_pipeline_injected_fault_via_raising_at_step():
    """The ft/faults injector composes with the pipeline: deterministic
    producer death at a chosen nominal step."""
    from repro.ft.faults import raising_at_step
    mk = raising_at_step(lambda s, sh: {"x": np.full((2,), s)}, 2)
    pipe = DataPipeline(mk, None, prefetch=1)
    assert next(pipe)[0] == 0
    assert next(pipe)[0] == 1
    with pytest.raises(RuntimeError, match="injected data fault"):
        next(pipe)
    pipe.close()


def test_pipeline_resume_matches_schedule_tail():
    """start_step > 0 reproduces the TAIL of smd_schedule exactly — same
    drop positions and counts — which is what makes chunked resume land on
    the same chunk layout as an uninterrupted run."""
    cfg = SMDConfig(enabled=True, drop_prob=0.5)
    seed, total, start = 7, 40, 17
    sched = smd_schedule(cfg, seed, total)
    mk = lambda step, shard: {"x": np.full((2,), step)}
    pipe = DataPipeline(mk, cfg, seed=seed, start_step=start)
    out = [next(pipe) for _ in range(total - start)]
    pipe.close()
    assert [s for s, _ in out] == list(range(start, total))
    got_kept = [b is not None for _, b in out]
    assert got_kept == [bool(k) for k in sched[start:]]
    assert sum(1 for k in got_kept if not k) == int((~sched[start:]).sum())


def test_pipeline_counts_one_smd_decision_per_nominal_step():
    """Across a decision-block edge (step 256), the pipeline still counts
    one timed decision per nominal step handed out, dropped or kept."""
    cfg = SMDConfig(enabled=True, drop_prob=0.5)
    seed, start, n = 11, 250, 12
    mk = lambda step, shard: {"x": np.full((2,), step)}
    pipe = DataPipeline(mk, cfg, seed=seed, start_step=start)
    out = [next(pipe) for _ in range(n)]
    pipe.close()
    assert [s for s, _ in out] == list(range(start, start + n))
    assert [b is not None for _, b in out] == \
        [bool(k) for k in smd_schedule(cfg, seed, start + n)[start:]]
    assert pipe.smd_decisions == n
    assert pipe.smd_decide_s > 0


def test_smd_iterator_resume_matches_schedule_tail():
    """SMDIterator at start_step > 0: same tail reproduction, and the
    underlying iterator advances only on kept steps (zero-overhead drops).
    The drop count over the window equals what Trainer.dropped_steps would
    accumulate (both are counts of False entries in the same schedule)."""
    cfg = SMDConfig(enabled=True, drop_prob=0.5)
    seed, total, start = 3, 32, 9
    sched = smd_schedule(cfg, seed, total)
    consumed = []
    def src():
        i = 0
        while True:
            consumed.append(i)
            yield {"i": i}
            i += 1
    it = SMDIterator(src(), cfg, seed, start_step=start)
    out = [next(it) for _ in range(total - start)]
    assert [s for s, _ in out] == list(range(start, total))
    assert [b is not None for _, b in out] == [bool(k) for k in sched[start:]]
    kept = int(sched[start:].sum())
    assert len(consumed) == kept               # drops never touch the source
    assert (total - start) - kept == int((~sched[start:]).sum())
