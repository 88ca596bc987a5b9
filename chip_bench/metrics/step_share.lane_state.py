"""step_share.lane_state (round body): the share of the window's device op
time whose instruction the program maps to the ``lane_state``
step (``steps.share``).  Silent where the program publishes no map.
In %."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import steps  # noqa: E402


def compute(records, trace):
    return steps.share(trace, "lane_state")
