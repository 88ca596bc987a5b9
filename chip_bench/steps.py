"""Readings of what the program publishes about itself: device time by
protocol step, and the set-up counters.

* ``share(trace, step)``: the window's op seconds (``trace["op_s"]``)
  whose HLO instruction the program maps to ``step``
  (``repro.obs.trace.op_steps``: each instruction's innermost
  ``jax.named_scope`` of ``repro.obs.trace.ROUND_STEPS``), over the
  sum of all op seconds, in %.  ``step=None`` reads the unscoped ops.
* ``counter(name)``: a counter of the program's default metrics
  registry (``repro.obs.metrics``), such as ``compile.lower_s``.

Both return None where the program publishes nothing of the kind, as a
program from before the steps were named does.
"""

from __future__ import annotations


def op_steps() -> dict:
    """The program's {instruction name: step}; empty where it has
    none."""
    try:
        from repro.obs import trace
    except ImportError:
        return {}
    read = getattr(trace, "op_steps", None)
    return read() if read is not None else {}


def share(trace, step):
    steps = op_steps()
    total = sum(trace["op_s"].values())
    if not steps or not total:
        return None
    mine = sum(t for op, t in trace["op_s"].items()
               if steps.get(op) == step)
    return 100.0 * mine / total


def counter(name: str):
    try:
        from repro.obs import metrics
    except ImportError:
        return None
    reg = metrics.default_registry()
    if name not in reg.names():
        return None
    return float(reg.counter(name).value)
