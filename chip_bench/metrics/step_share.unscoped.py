"""step_share.unscoped (round body): the share of the window's device
op time whose instruction the program maps to no step
(``steps.share``).  Silent where the program publishes no map.  In %."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import steps  # noqa: E402


def compute(records, trace):
    return steps.share(trace, None)
