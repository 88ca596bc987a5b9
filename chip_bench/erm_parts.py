"""Device time of the parts of the center's ERM, read from what the
program publishes about itself.

``share(trace, part)``: the window's op seconds (``trace["op_s"]``)
whose HLO instruction the program maps to ``part``
(``repro.obs.trace.op_parts``: each instruction's innermost
``jax.named_scope`` of ``repro.obs.trace.ERM_PARTS``), over the sum of
all op seconds, in %.  None where the program publishes no such map,
as a program from before the parts were named does.
"""

from __future__ import annotations


def op_parts() -> dict:
    """The program's {instruction name: part}; empty where it has
    none."""
    try:
        from repro.obs import trace
    except ImportError:
        return {}
    read = getattr(trace, "op_parts", None)
    return read() if read is not None else {}


def share(trace, part):
    parts = op_parts()
    total = sum(trace["op_s"].values())
    if not parts or not total:
        return None
    mine = sum(t for op, t in trace["op_s"].items() if parts.get(op) == part)
    return 100.0 * mine / total
