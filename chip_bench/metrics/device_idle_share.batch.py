"""device_idle_share.batch (device): 1 − busy / window from the device
trace, the mean over the cell's devices.  In %."""


def compute(records, trace):
    return 100.0 * (1 - trace["busy_s"] / trace["window_s"])
