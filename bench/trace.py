"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A device plane (``/device:TPU:<n>``) holds a line ``XLA Ops`` of the
operations that ran on that device, nested (a ``while`` holds the ops of
its body); a host plane (``/host:CPU``) holds one line per host thread.
The benchmark brackets its traced window with a host annotation
(``WINDOW``), so the window is read on the trace's own clock.

Per device the reduction gives:
* ``busy``: the union of the intervals in which an op ran, clipped to the
  window;
* ``ops``: every leaf op (one that holds no other op) with its short name,
  its HLO kind, its duration and its start; ``nested``: the others (a
  ``while`` that holds its body's ops);
* ``idle_gaps``: each gap between busy intervals, labelled by what the
  main host thread was doing over most of it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
Interval = Tuple[int, int]


@dataclass
class Op:
    name: str            # the HLO instruction name, e.g. "%fusion.12"
    kind: str            # its opcode, e.g. "fusion", "custom-call"
    start: int           # ns on the trace clock
    dur: int             # ns
    text: str = ""       # the full event name (HLO text)


@dataclass
class Device:
    index: int
    ops: List[Op] = field(default_factory=list)        # leaves
    nested: List[Op] = field(default_factory=list)     # ops holding others
    busy: List[Interval] = field(default_factory=list)

    def busy_ns(self) -> int:
        return sum(e - s for s, e in self.busy)


@dataclass
class Trace:
    window: Interval
    devices: List[Device]
    gaps: List[Tuple[str, int]]          # (host activity, ns), all devices

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(d.busy_ns() for d in self.devices) * 1e-9 / max(
            len(self.devices), 1)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def op_of(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an XLA op event's HLO text
    ``%name = type opcode(...)``; plain names pass through."""
    m = re.match(r"\s*(%?[\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(",
                 text)
    if m:
        return m.group(1), m.group(2)
    name = text.split(" ")[0]
    return name, re.sub(r"[.\d]+$", "", name.lstrip("%")) or name


def leaves(events: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """The events that hold no other event (start, end, name), sorted."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, n) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue                      # holds the next event
        out.append((s, e, n))
    return out


def _events(line) -> List[Tuple[int, int, str]]:
    return [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
            for ev in line.events]


def reduce(planes, gap_min_ns: int = 20_000) -> Trace:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` of ``name``, ``start_ns`` and
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    host_lines: Dict[str, List[Tuple[int, int, str]]] = {}
    device_events: Dict[int, List[Tuple[int, int, str]]] = {}
    window: Optional[Interval] = None
    for plane in planes:
        m = DEVICE_PLANE.search(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device_events[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                evs = _events(line)
                for s, e, n in evs:
                    if n == WINDOW:
                        window = (s, e)
                host_lines[line.name] = evs
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = window
    devices = []
    for idx in sorted(device_events):
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in device_events[idx]
               if min(e, hi) > max(s, lo)]
        dev = Device(idx, busy=union((s, e) for s, e, _ in evs))
        leaf = set(leaves(evs))
        for s, e, text in sorted(evs):
            name, kind = op_of(text)
            (dev.ops if (s, e, text) in leaf else dev.nested).append(
                Op(name, kind, s, e - s, text))
        devices.append(dev)
    gaps = []
    main = _main_thread(host_lines)
    for dev in devices:
        edges = [lo] + [t for iv in dev.busy for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s >= gap_min_ns:
                gaps.append((_activity(main, s, e), e - s))
    return Trace(window, devices, gaps)


def _main_thread(host_lines) -> List[Tuple[int, int, str]]:
    """The events of the host thread that drives the device: the one that
    holds the window annotation."""
    for evs in host_lines.values():
        if any(n == WINDOW for _, _, n in evs):
            return sorted(evs)
    return []


def _activity(events, lo: int, hi: int) -> str:
    """Name of the innermost main-thread event that covers most of
    [lo, hi), or ``host: none`` where no event does."""
    best, best_key = "host: none", None
    for s, e, n in events:
        if s >= hi:
            break
        cover = min(e, hi) - max(s, lo)
        if n == WINDOW or cover * 2 < (hi - lo):
            continue
        key = (cover, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = n, key
    return best


def load(trace_dir: str, gap_min_ns: int = 20_000) -> Trace:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found "
                         f"{len(files)}")
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(files[0]).planes, gap_min_ns)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` ops that took most device time, summed over the devices
    and averaged per device: [[name, seconds], ...]."""
    total: Dict[str, int] = {}
    for dev in trace.devices:
        for op in dev.ops:
            key = f"{op.kind} {op.name}"
            total[key] = total.get(key, 0) + op.dur
    k = max(len(trace.devices), 1)
    return [[name, ns * 1e-9 / k] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle device time by what the host was doing, summed over the gaps
    and averaged per device: [[activity, seconds], ...]."""
    total: Dict[str, int] = {}
    for name, ns in trace.gaps:
        total[name] = total.get(name, 0) + ns
    k = max(len(trace.devices), 1)
    return [[name, ns * 1e-9 / k] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
