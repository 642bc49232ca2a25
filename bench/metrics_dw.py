"""What the PSG depthwise readers in ``bench/metrics`` share: the kernel's
calls among a device's ops and the least work of each.

The calls are the Mosaic custom calls (``tpu_custom_call``) whose first
operand has rank 3: the ``(9, M, C)`` tap codes of
``kernels/psg_depthwise.py``.  The PSG matmul kernel's operands have rank
2, and no other Mosaic kernel runs on the chip's conv path.  The sites
come from the configuration's arch and the per-chip batch
(``bench/families/cifar_mbv2.dw_sites``), since the run's record carries
only the matmul sites; an arch without depthwise convs has none.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from bench.metrics_common import operand_shapes, psg_kernel_ops

TAPS = 9
ROW_PAD = 1024          # the kernel pads rows to its row tile, at most this


def dw_kernel_ops(device) -> List:
    """The depthwise kernel calls among a device's ops."""
    out = []
    for op in psg_kernel_ops(device):
        shapes = operand_shapes(op.text)
        if shapes and len(shapes[0][1]) == 3 and shapes[0][1][0] == TAPS:
            out.append(op)
    return out


def sites(record) -> Optional[List[dict]]:
    """The depthwise sites of one chip's step, each with its rows ``M``
    and the batch ``B``, or None where the arch has none."""
    arch = record.get("arch") or {}
    if arch.get("kind") != "mobilenetv2":
        return None
    from bench.families.cifar_mbv2 import dw_sites
    b = int(record["batch"]) // int(record.get("chips", 1))
    return [dict(s, B=b, M=b * s["out_hw"] * s["out_hw"])
            for s in dw_sites(arch)]


def match(shapes: Sequence, dws: Sequence[dict]) -> Optional[dict]:
    """The site whose rows and channels fit the ``(9, M, C)`` first operand
    with the least padding, or None."""
    if not shapes or len(shapes[0][1]) != 3:
        return None
    _, m, c = shapes[0][1]
    fits = [s for s in dws if s["M"] <= m < s["M"] + ROW_PAD
            and s["C"] <= c]
    if not fits:
        return None
    return min(fits, key=lambda s: (m - s["M"]) + (c - s["C"]))


def least_seconds(site, psg, peak) -> float:
    """The least time of one site's weight-gradient work: two products,
    2 x 2 x 9 M C FLOPs; each activation read once at its code widths
    (the input B in_hw^2 C, not the nine taps of each output), the output
    gradient once, two float32 (9, C) products written."""
    C, m = site["C"], site["M"]
    flops = 2 * 2.0 * TAPS * m * C
    nbytes = (site["B"] * site["in_hw"] ** 2 * C
              * (psg["bits_x_msb"] + psg["bits_x"]) / 8.0
              + m * C * (psg["bits_g_msb"] + psg["bits_g"]) / 8.0
              + 2 * TAPS * C * 4.0)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
