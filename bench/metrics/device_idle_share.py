"""device_idle_share (%): the share of the traced window in which no op ran
on the device, 1 - union of busy intervals over the window, averaged over
the cell's devices."""


def read(record, trace):
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
