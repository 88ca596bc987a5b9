"""histogram_roofline (kernels): the histogram kernel's share of its
roofline — the least time the chip could take for the launches in the
window (for each, the larger of operations / peak FLOP/s and bytes /
peak HBM bandwidth, from roofline/histogram.py and peaks.py) over the
kernel's device time.  Silent where the trace holds no launch of it.
In %."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "roofline"))
sys.path.insert(0, os.path.join(HERE, ".."))

import histogram  # noqa: E402
import peaks  # noqa: E402


def compute(records, trace):
    config = records["config"]
    p = peaks.peak(records["device_kind"])
    least = spent = 0.0
    for op, t in trace["op_s"].items():
        if op.split(".")[0] != histogram.TRACE_NAME:
            continue
        units, N = histogram.from_result_dims(trace["op_dims"][op])
        ops, nbytes = histogram.launch(units, N, config["coreset"],
                                       config["features"], config["bins"])
        least += trace["op_n"][op] * max(ops / p["flops_per_s"],
                                         nbytes / p["hbm_bytes_per_s"])
        spent += t
    return 100.0 * least / spent if spent else None
