"""psg_kernel_share (%): device time of the PSG code-product kernels (the
Mosaic custom calls of kernels/psg_matmul.py) over the device's busy time
in the traced window, averaged over the devices."""
from bench.metrics_common import psg_kernel_ops


def read(record, trace):
    if trace is None or not trace.devices:
        return None
    if not record.get("psg", {}).get("enabled"):
        return None
    busy = sum(d.busy_ns() for d in trace.devices)
    kern = sum(op.dur for d in trace.devices for op in psg_kernel_ops(d))
    if busy <= 0 or kern <= 0:
        return None
    return 100.0 * kern / busy
