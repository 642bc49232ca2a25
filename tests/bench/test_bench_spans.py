"""The readers of idle device time by host span and of the SMD decision
time, on hand-built traces and histories."""
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402  (puts the repository on sys.path)

from bench import spec as S  # noqa: E402
from bench import trace as T  # noqa: E402
from repro.training.trainer import SPANS  # noqa: E402

BENCH = S.Benchmark(bench_tiny.REPO)
FUSION = '%fusion.3 = f32[128,32,32,16]{0,3,2,1} fusion(f32[16] %p), kind=kLoop'


def reader(name):
    return BENCH.reader(name)


def module(name):
    return S._module(BENCH.file("metrics", f"{name}.py"), f"spans_{name}")


@dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: dict = field(default_factory=dict)


@dataclass
class Line:
    name: str
    events: List[Ev]


@dataclass
class Plane:
    name: str
    lines: List[Line]


def hand_trace(labels):
    """Two devices over a 10 us window, idle in the labelled gaps."""
    return T.Trace(window=(0, 10_000), devices=[T.Device(0), T.Device(1)],
                   gaps=labels)


GAPS = [("trainer.collect", 3000), ("trainer.stack", 800),
        ("trainer.dispatch", 400), ("trainer.sync", 600),
        ("trainer.checkpoint", 200), ("PjitFunction(chunk_step)", 100),
        ("host: none", 300), ("CommonPjRtBuffer::ToLiteral", 500)]


@pytest.mark.parametrize("metric,ns", [
    ("idle_pipeline_share", 3000),
    ("idle_loop_share", 800 + 400 + 600 + 200)])
def test_idle_shares_sum_their_spans_gaps(metric, ns):
    # summed over the devices' gaps, averaged per device, over the window
    got = reader(metric)({}, hand_trace(GAPS))
    assert got == pytest.approx(100 * ns / 2 / 10_000)


@pytest.mark.parametrize("metric", ["idle_pipeline_share", "idle_loop_share"])
@pytest.mark.parametrize("trace", [
    None,
    hand_trace([("host: none", 5000)]),
    T.Trace(window=(0, 10_000), devices=[], gaps=GAPS)],
    ids=["untraced", "no_spans", "no_devices"])
def test_idle_shares_read_nothing_without_the_spans(metric, trace):
    assert reader(metric)({}, trace) is None


@pytest.mark.parametrize("metric,other", [
    ("idle_pipeline_share", "trainer.sync"),
    ("idle_loop_share", "trainer.collect")])
def test_idle_share_is_zero_when_its_spans_cover_no_gap(metric, other):
    got = reader(metric)({}, hand_trace([(other, 4000)]))
    assert got == 0.0


def test_span_names_the_readers_use_are_the_programs():
    names = (module("idle_pipeline_share").SPANS
             + module("idle_loop_share").SPANS)
    assert set(names) <= set(SPANS)
    assert "trainer.collect" in names
    prefix = S._module(bench_tiny.REPO / "bench" / "idle_spans.py",
                       "spans_idle").PROGRAM
    assert all(n.startswith(prefix) for n in names)


def traced_planes():
    """A window 0-10000 on one device busy 0-2000, 4400-4500 (a batch made
    on the device) and 6000-9000.  The main thread collects (2000-5500,
    stacking 4500-5500), dispatches (5500-6000) and from 9000 syncs.  The
    pipeline thread's line has a name of its own."""
    main = Line("python3", [Ev(T.WINDOW, 0, 10_000),
                            Ev("trainer.collect", 2000, 3500),
                            Ev("trainer.stack", 4500, 1000),
                            Ev("trainer.dispatch", 5500, 500),
                            Ev("trainer.sync", 9000, 1000)])
    pipe = Line("repro-pipeline", [Ev("pipeline.smd_decide", 2000, 2000),
                                   Ev("pipeline.make_batch", 4000, 500)])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [Ev(FUSION, 0, 2000),
                                                   Ev(FUSION, 4400, 100),
                                                   Ev(FUSION, 6000, 3000)])])
    return [dev, Plane("/host:CPU", [main, pipe])]


def test_reduced_trace_labels_gaps_by_the_program_spans():
    tr = T.reduce(traced_planes(), gap_min_ns=100)
    # a gap inside one phase takes the phase's name; one that crosses from
    # stacking into dispatch takes the phase that covers most of it
    assert tr.gaps == [("trainer.collect", 2400), ("trainer.stack", 1500),
                       ("trainer.sync", 1000)]
    pipeline = reader("idle_pipeline_share")({}, tr)
    loop = reader("idle_loop_share")({}, tr)
    idle = reader("device_idle_share")({}, tr)
    assert (pipeline, loop, idle) == pytest.approx((24.0, 25.0, 49.0))
    assert pipeline + loop <= idle


def hist(pairs):
    return [{"total_loss": 1.0, "wall_s": 0.1, "smd_decide_s": s,
             "smd_decisions": n} for s, n in pairs]


def test_smd_decide_ms_is_seconds_over_decisions():
    # two chunks of two steps: 3 decisions in 6 ms, then 1 in 1 ms
    record = {"history": hist([(0.003, 1.5), (0.003, 1.5),
                               (0.0005, 0.5), (0.0005, 0.5)])}
    assert reader("smd_decide_ms")(record, None) == pytest.approx(7 / 4)


@pytest.mark.parametrize("history", [
    [], [{"total_loss": 1.0, "wall_s": 0.1}], hist([(0.0, 0.0)])],
    ids=["empty", "no_counter", "smd_off"])
def test_smd_decide_ms_reads_nothing_without_decisions(history):
    assert reader("smd_decide_ms")({"history": history}, None) is None


def test_new_metrics_are_appended_to_the_benchmark():
    spec = S.load_json(bench_tiny.REPO / "BENCHMARK.json")
    S.validate(spec)
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-3:] == ["idle_pipeline_share", "idle_loop_share",
                          "smd_decide_ms"]
    by = {m["name"]: m for m in spec["per_layer"]}
    assert by["smd_decide_ms"]["workloads"] == ["resnet74.e2train"]
    for m in names[-3:]:
        assert by[m]["better"] == "lower"
        assert by[m]["moves"] == "images_per_s"
