"""Idle device time by the program's host span, for the per-layer metrics
in ``bench/metrics`` that read it."""
from __future__ import annotations

from typing import Iterable, Optional

PROGRAM = "trainer."        # prefix of the program's main-thread spans


def idle_share(trace, spans: Iterable[str]) -> Optional[float]:
    """Idle device time in the gaps labelled by one of ``spans``, over the
    window, in %, averaged over the devices.  ``None`` where no gap carries
    a program span's name: a program without the spans."""
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    if not any(name.startswith(PROGRAM) for name, _ in trace.gaps):
        return None
    spans = set(spans)
    ns = sum(ns for name, ns in trace.gaps if name in spans)
    return 100.0 * ns * 1e-9 / len(trace.devices) / trace.window_s
