"""dw_kernel_roofline (%): the least time the PSG depthwise weight-gradient
work of the traced window needs on this chip over the device time of the
depthwise kernel's calls.

Each call (a Mosaic custom call whose first operand is the rank-3 (9, M,
C) tap codes) is matched to a depthwise site of the model by its rows M
and channels C (bench/metrics_dw.py).  A call does both products of its
site, so it is given the site's whole work: 2 x 2 x 9 M C FLOPs, and
bytes that read each activation once at its code widths (B in_hw^2 C
(bits_x_msb + bits_x) / 8, not the nine taps), the output gradient once
(M C (bits_g_msb + bits_g) / 8) and two float32 (9, C) products.  The
least time of a call is the larger of FLOPs over the bf16 peak and bytes
over HBM bandwidth (bench/peaks.json).  Container dtypes, the tap stacks
and padding are never counted."""
from bench.metrics_dw import dw_kernel_ops, least_seconds, match, sites
from bench.metrics_common import operand_shapes


def read(record, trace):
    peak, psg = record.get("peak"), record.get("psg", {})
    dws = sites(record)
    if trace is None or not peak or not psg.get("enabled") or dws is None:
        return None
    least = spent = 0.0
    for dev in trace.devices:
        for op in dw_kernel_ops(dev):
            site = match(operand_shapes(op.text), dws)
            if site is None:
                return None
            least += least_seconds(site, psg, peak)
            spent += op.dur * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
