"""dw_kernel_share (%): device time of the PSG depthwise kernel calls
(kernels/psg_depthwise.py) over the device's busy time in the traced
window, averaged over the devices.  The reader keys on the Mosaic custom
calls whose first operand has rank 3, the (9, M, C) tap codes
(bench/metrics_dw.py)."""
from bench.metrics_dw import dw_kernel_ops, sites


def read(record, trace):
    if trace is None or not trace.devices or sites(record) is None:
        return None
    if not record.get("psg", {}).get("enabled"):
        return None
    busy = sum(d.busy_ns() for d in trace.devices)
    kern = sum(op.dur for d in trace.devices for op in dw_kernel_ops(d))
    if busy <= 0 or kern <= 0:
        return None
    return 100.0 * kern / busy
