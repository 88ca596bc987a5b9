"""Operations and bytes of the tree histogram kernel, from the problem.

One launch builds, for each of ``units`` (task, party) pairs, the
weighted histograms of one tree level: ``c`` coreset rows of ``F``
features on ``Q`` bins, for ``N`` nodes, in two channels (w and w·y).
What the problem needs, whatever the kernel's blocks and padding:

* operations: one add per channel, node, row and feature
  (each row falls in one bin of each feature): 2·N·c·F;
* bytes: read the rows (c·F) and the routed weights (2·N·c), write the
  histograms (2·N·F·Q), all float32.

A depth-D tree level l has N = 2^l nodes, so one wire round launches
the kernel D times, with N = 1, 2, …, 2^(D-1); each launch's units and
N are read from its result's shape in the trace, the rows, features
and bins from the configuration.
"""

from __future__ import annotations

# the name the device trace gives the kernel's launches: the HLO
# custom call takes the name of the jitted function around the
# pallas_call, with a numeric suffix per call site
TRACE_NAME = "hist_batched_pallas"


def launch(units: int, N: int, c: int, F: int, Q: int) -> tuple[int, int]:
    """(operations, bytes) of one launch."""
    ops = 2 * N * c * F
    nbytes = 4 * (c * F + 2 * N * c + 2 * N * F * Q)
    return units * ops, units * nbytes


def from_result_dims(dims: list) -> tuple[int, int]:
    """(units, N) of a launch from its result's dimensions,
    ``[*units, feature blocks, 2·N, padded features × bins]``."""
    units = 1
    for d in dims[:-3]:
        units *= d
    return units, dims[-2] // 2
