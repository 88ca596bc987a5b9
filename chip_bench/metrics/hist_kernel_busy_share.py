"""hist_kernel_busy_share (kernels): the histogram kernel's device time
over the device's busy time.  Silent where the trace holds no launch of
it.  In %."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "roofline"))

import histogram  # noqa: E402


def compute(records, trace):
    t = sum(v for k, v in trace["op_s"].items()
            if k.split(".")[0] == histogram.TRACE_NAME)
    return 100.0 * t / trace["busy_s"] if t else None
