"""Helpers shared by the per-layer metric readers in ``bench/metrics``."""
from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def psg_kernel_ops(device) -> List:
    """The PSG code-product kernel calls among a device's ops: the Mosaic
    custom calls (the only ones E2-Train's conv path lowers to; XLA's own
    custom calls, such as ``ConcatBitcast``, have other targets)."""
    return [op for op in device.ops if op.kind == "custom-call"
            and MOSAIC in op.text]


def operand_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each operand in an op's HLO text, in order."""
    head, _, args = text.partition("custom-call(")
    if not args:
        return []
    args = args.split("custom_call_target", 1)[0]
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in SHAPE.findall(args)]


def match_site(shapes: Sequence, sites: Sequence[dict]) -> Optional[dict]:
    """The site whose (N, din) and (N, dout) fit the first two operands
    with the least padding, or None."""
    if len(shapes) < 2 or len(shapes[0][1]) != 2 or len(shapes[1][1]) != 2:
        return None
    (n, dinp), (n2, doutp) = shapes[0][1], shapes[1][1]
    fits = [s for s in sites if s["N"] <= n == n2 and s["N"] > n - 512
            and s["din"] <= dinp and s["dout"] <= doutp]
    if not fits:
        return None
    return min(fits, key=lambda s: (dinp - s["din"]) + (doutp - s["dout"]))
