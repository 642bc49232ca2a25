"""Host spans of the chunked loop and its data pipeline, read back from a
profile of a tiny chunked run with SMD on."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.config import (E2TrainConfig, Experiment, ModelConfig,
                               SMDConfig, TrainConfig)
from repro.core.smd import smd_schedule
from repro.data.synthetic import MarkovLMTask, make_lm_batch
from repro.training.train_step import init_train_state
from repro.training.trainer import (CHECKPOINT, COLLECT, DISPATCH,
                                    MAKE_BATCH, SMD_DECIDE, SPANS, STACK,
                                    SYNC, Trainer)

K = 2
CHUNK_SPANS = (COLLECT, STACK, DISPATCH, SYNC, CHECKPOINT)
RUN = "test.run"        # the test's own span around one Trainer.run call


def _exp():
    model = ModelConfig(name="t", family="dense", num_layers=1, d_model=16,
                        num_heads=2, num_kv_heads=1, d_ff=32, vocab_size=16,
                        dtype="float32")
    return Experiment(model=model,
                      e2=E2TrainConfig(smd=SMDConfig(enabled=True,
                                                     drop_prob=0.5)),
                      train=TrainConfig(global_batch=2, seq_len=8,
                                        total_steps=64, schedule="constant"),
                      task="lm")


def _nominal(exp, tail_drops):
    """Nominal steps holding 3 whole chunks, then up to ``tail_drops``
    drops after the last executed step (fewer where a kept step follows)."""
    keep = smd_schedule(exp.e2.smd, exp.train.seed, 64)
    n = int(np.flatnonzero(keep)[3 * K - 1]) + 1
    while tail_drops and not keep[n]:
        n, tail_drops = n + 1, tail_drops - 1
    return n, keep[:n]


def _trace(tmp, fn):
    """The program's spans, and ``RUN`` around ``fn``, in a profile of
    ``fn()``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(RUN):
            fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = []                    # (span, start, end, args, line index)
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SPANS + (RUN,):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (line.name, i)))
    return sorted(out, key=lambda e: e[1])


def _of(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module", params=[0, 2], ids=["whole", "tail_drops"])
def traced(request, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    exp = _exp()
    task = MarkovLMTask(vocab=exp.model.vocab_size)
    mk = lambda s, sh: make_lm_batch(task, 0, s, sh, 2, 8)   # noqa: E731
    n, keep = _nominal(exp, request.param)
    tr = Trainer(exp, init_train_state(jax.random.PRNGKey(0), exp), mk,
                 chunk_steps=K, checkpoint_dir=str(tmp / "ckpt"),
                 checkpoint_every=1)
    spans = _trace(tmp, lambda: tr.run(n))
    return tr, spans, n, keep


def test_every_span_appears(traced):
    _, spans, _, _ = traced
    assert {s[0] for s in spans} == set(SPANS) | {RUN}


def test_chunk_spans_nest_in_run_with_increasing_ids(traced):
    _, spans, _, _ = traced
    (run,) = _of(spans, RUN)
    for name, s, e, args, line in spans:
        if name in CHUNK_SPANS:
            assert run[1] <= s <= e <= run[2] and line == run[4], name
            assert set(args) >= {"chunk"}
    # the phases follow one another: no program span encloses another
    # phase, so each idle gap is labelled by the phase it falls in
    phases = [p for p in spans if p[0] in CHUNK_SPANS and p[0] != STACK]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a[0], b[0])
    for name in (DISPATCH, SYNC):
        assert [s[3]["chunk"] for s in _of(spans, name)] == [0, 1, 2]
    ids = [s[3]["chunk"] for s in _of(spans, COLLECT)]
    assert ids[:3] == [0, 1, 2] and ids == sorted(ids)
    for st in _of(spans, STACK):
        (col,) = [c for c in _of(spans, COLLECT) if c[1] <= st[1] <= c[2]]
        assert col[3]["chunk"] == st[3]["chunk"] and st[2] <= col[2]


def test_collect_spans_cover_the_nominal_steps(traced):
    _, spans, n, _ = traced
    cover = [(c[3]["first_step"], c[3]["last_step"])
             for c in _of(spans, COLLECT)]
    assert cover[0][0] == 0 and cover[-1][1] == n - 1
    for (_, last), (first, _) in zip(cover, cover[1:]):
        assert first == last + 1


def test_pipeline_spans_match_the_smd_schedule(traced):
    _, spans, n, keep = traced
    decided = [s[3]["step"] for s in _of(spans, SMD_DECIDE)]
    made = [s[3]["step"] for s in _of(spans, MAKE_BATCH)]
    # the producer may run ahead of the consumer by the prefetch depth
    assert [s for s in decided if s < n] == list(range(n))
    assert [s for s in made if s < n] == list(np.flatnonzero(keep))
    assert len(decided) - n <= 3
    # on a line of their own, which a profile reader can tell apart
    (run,) = _of(spans, RUN)
    lines = {s[4] for s in spans if s[0] in (SMD_DECIDE, MAKE_BATCH)}
    assert run[4] not in lines
    assert run[4][0] not in {name for name, _ in lines}


def test_smd_counters_cover_the_consumed_steps(traced):
    tr, _, n, _ = traced
    assert len(tr.history) == tr.executed_steps == 3 * K
    assert sum(h["smd_decisions"] for h in tr.history) == pytest.approx(n)
    assert sum(h["smd_decide_s"] for h in tr.history) > 0
    # spread over the chunk's executed steps, as wall_s is
    for chunk in (tr.history[:K], tr.history[K:2 * K]):
        assert chunk[0]["smd_decisions"] == chunk[-1]["smd_decisions"]


def test_span_args_are_host_ints(traced):
    _, spans, _, _ = traced
    for _, _, _, args, _ in spans:
        assert all(isinstance(v, int) for v in args.values()), args


def test_per_step_loop_has_only_the_run_span(tmp_path):
    # the per-step loop opens no span of its own inside the run call
    exp = _exp()
    task = MarkovLMTask(vocab=exp.model.vocab_size)
    mk = lambda s, sh: make_lm_batch(task, 0, s, sh, 2, 8)   # noqa: E731
    tr = Trainer(exp, init_train_state(jax.random.PRNGKey(0), exp), mk)
    spans = _trace(tmp_path, lambda: tr.run(4))
    assert [s[0] for s in spans] == [RUN]
