"""The trace reduction and the readers that use it, on a hand-built
trace with the planes, lines and HLO event names of a TPU trace."""
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402,F401  (puts the repository on sys.path)

from bench import trace as T  # noqa: E402
from bench.metrics_common import match_site, operand_shapes  # noqa: E402

KERNEL = ('%custom-call.7 = f32[256,16]{1,0:T(8,128)} custom-call(s8[131072,'
          '256]{1,0:T(8,128)(4,1)} %a, s16[131072,16]{1,0:T(8,128)(2,1)} %b),'
          ' custom_call_target="tpu_custom_call"')
FUSION = '%fusion.3 = f32[128,32,32,16]{0,3,2,1} fusion(f32[16] %p), kind=kLoop'
WHILE = '%while.1 = (s32[], f32[2]) while((s32[], f32[2]) %t), body=%b'
ALLREDUCE = '%all-reduce.2 = f32[64]{0} all-reduce(f32[64]{0} %g), to_apply=%add'


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


def tpu(index, events):
    return Plane(f"/device:TPU:{index}", [Line("Steps", []),
                                          Line("XLA Ops", events)])


def host():
    return Plane("/host:CPU", [
        Line("main/1", [Ev(T.WINDOW, 1000, 10000),
                        Ev("PJRT_LoadedExecutable_Execute", 1000, 500),
                        Ev("CommonPjRtBuffer::ToLiteral", 6000, 2000)]),
        Line("pipeline/2", [Ev("bench_make_batch", 6000, 3000)])])


def planes():
    # device 0: a while (1500-5500) holding a fusion and a kernel, idle
    # 5500-9000 while the host copies a buffer, a fusion 9000-10500
    # (clipped at the window's end, 11000)
    dev0 = [Ev(WHILE, 1500, 4000), Ev(FUSION, 1500, 1000),
            Ev(KERNEL, 3000, 2500), Ev(FUSION, 9000, 1500),
            Ev(FUSION, 500, 600)]
    dev1 = [Ev(ALLREDUCE, 1000, 2000), Ev(FUSION, 2000, 500),
            Ev(FUSION, 6000, 1000)]
    return [Plane("/host:metadata", []), tpu(0, dev0), tpu(1, dev1), host()]


def test_op_names_and_kinds():
    assert T.op_of(KERNEL) == ("%custom-call.7", "custom-call")
    assert T.op_of(WHILE) == ("%while.1", "while")
    assert T.op_of(FUSION)[1] == "fusion"
    assert T.op_of("copy.4")[1] == "copy"


def test_busy_idle_and_leaves():
    tr = T.reduce(planes(), gap_min_ns=100)
    assert tr.window == (1000, 11000)
    d0, d1 = tr.devices
    # the early fusion is clipped to the window: busy from 1000
    assert d0.busy == [(1000, 1100), (1500, 5500), (9000, 10500)]
    assert d0.busy_ns() == 100 + 4000 + 1500
    # the while holds other ops: only leaves count as ops
    assert [op.kind for op in d0.ops] == ["fusion", "fusion", "custom-call",
                                          "fusion"]
    assert d1.busy == [(1000, 3000), (6000, 7000)]
    assert tr.window_s == pytest.approx(1e-5)
    assert tr.busy_s() == pytest.approx((5600 + 3000) / 2 * 1e-9)


def test_idle_gaps_are_labelled_by_the_host_thread():
    tr = T.reduce(planes(), gap_min_ns=100)
    by = dict(T.top_gaps(tr))
    # 5500-9000 on device 0: the main thread copies to the host over most
    # of it (6000-8000 of 3500 ns)
    assert "CommonPjRtBuffer::ToLiteral" in by
    # 10500-11000 on device 0: nothing on the main thread
    assert by["host: none"] > 0
    assert sum(by.values()) == pytest.approx(
        (10000 - 5600 + 10000 - 3000) / 2 * 1e-9)


def test_top_ops_rank_by_device_time():
    tr = T.reduce(planes(), gap_min_ns=100)
    top = T.top_ops(tr, n=2)
    assert top[0][0] == "fusion %fusion.3"
    assert top[1][0] == "custom-call %custom-call.7"
    assert top[1][1] == pytest.approx(2500 / 2 * 1e-9)


def test_no_window_annotation_is_an_error():
    ps = planes()[:-1]
    with pytest.raises(ValueError, match="bench_window"):
        T.reduce(ps)


def test_readers_on_the_hand_built_trace():
    from bench import spec as S
    b = S.Benchmark(bench_tiny.REPO)
    tr = T.reduce(planes(), gap_min_ns=100)
    idle = b.reader("device_idle_share")({}, tr)
    assert idle == pytest.approx(100 * (1 - (5600 + 3000) / 2 / 10000))
    share = b.reader("psg_kernel_share")({"psg": {"enabled": True}}, tr)
    assert share == pytest.approx(100 * 2500 / (5600 + 3000))


def test_kernel_operands_map_to_the_model_site():
    shapes = operand_shapes(KERNEL)
    assert shapes == [("s8", (131072, 256)), ("s16", (131072, 16))]
    sites = [{"N": 131072, "din": 144, "dout": 16},
             {"N": 131072, "din": 27, "dout": 16},
             {"N": 32768, "din": 288, "dout": 32}]
    assert match_site(shapes, sites) == sites[0]
    assert match_site([("s8", (8192, 640))], sites) is None


def test_roofline_counts_the_sites_work_not_the_containers():
    from bench import spec as S
    b = S.Benchmark(bench_tiny.REPO)
    psg = {"enabled": True, "bits_x": 8, "bits_g": 16, "bits_x_msb": 4,
           "bits_g_msb": 10}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    record = {"psg": psg, "peak": peak,
              "psg_sites": [{"N": 131072, "din": 144, "dout": 16}]}
    tr = T.reduce(planes(), gap_min_ns=100)
    n, din, dout = 131072, 144, 16
    nbytes = (n * din * 12 + n * dout * 26) / 8 + 2 * 4 * din * dout
    least = 0.5 * max(4.0 * n * din * dout / 197e12, nbytes / 819e9)
    got = b.reader("psg_kernel_roofline")(record, tr)
    assert got == pytest.approx(100 * least / 2500e-9)
