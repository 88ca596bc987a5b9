"""erm_part_share.split_search (round body): the share of the window's
device op time whose instruction the program maps to the
``split_search`` part of the center's ERM, the best splits and the
leaves' prefix sums over the merged histograms (``erm_parts.share``).
Silent where the program publishes no such map.  In %."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import erm_parts  # noqa: E402


def compute(records, trace):
    return erm_parts.share(trace, "split_search")
