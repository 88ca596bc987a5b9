"""Reduce a profiler trace of one window to the numbers metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` wrote.  The window is
the host span ``window`` that the harness opens around the timed loop;
everything is clipped to it.  Per device (a ``/device:TPU:<n>`` plane,
its ``XLA Ops`` line):

* busy intervals (the union of op events, a ``while`` loop's included)
  and idle intervals;
* device seconds, launches and result dimensions per op (the HLO
  instruction's name; control-flow containers such as ``while`` are
  left out, their bodies' ops are counted);
* collective seconds, and the part of them with no other op running on
  that device (exposed);
* the longest idle gaps, each labelled by the benchmark's host span
  (``stage``, ``engine``, ``finalize``) open at the gap's middle, or
  ``host`` where none is.

Device-wide numbers are means over the devices that ran any op.
"""

from __future__ import annotations

import collections
import glob
import gzip
import os
import re
import warnings

SPANS = ("stage", "engine", "finalize")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce", re.I)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def load(path: str) -> list:
    """[(plane, line, [(name, start_ns, dur_ns, stats)])] of a trace
    file (``.xplane.pb``, or gzipped), every event's stats a dict."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():
        # jaxlib's event-stats type, built on first use, warns that it
        # has no __module__; under warnings-as-errors that aborts
        warnings.filterwarnings("ignore", "builtin type .* __module__",
                                DeprecationWarning)
        for plane in data.planes:
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns),
                        dict(e.stats)) for e in line.events]
                out.append((plane.name, line.name, evs))
    return out


def _union(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _minus(a: list, b: list) -> list:
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(iv, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """An op's name: the HLO instruction's name where the event carries
    the instruction's text (``%fusion.225 = f32[...] fusion(...)``)."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def base_name(op: str) -> str:
    """The op's name without the numeric suffix the compiler gives each
    instance (``hist_batched_pallas.24`` → ``hist_batched_pallas``)."""
    return re.sub(r"(\.\d+)+$", "", op)


def out_dims(event_name: str) -> list:
    """The dimensions of the op's (first) result, from its HLO text."""
    m = re.search(r" = \(?\w+\[([\d,]*)\]", event_name)
    return [int(d) for d in m.group(1).split(",") if d] if m else []


CONTAINER = re.compile(r"(^|\s)(while|conditional|call)\(")


def reduce_lines(lines: list) -> dict:
    """The reduction of :func:`load`'s output (see module docstring)."""
    host = [ev for plane, _, evs in lines if not plane.startswith("/device")
            for ev in evs]
    windows = [(s, s + d) for name, s, d, _ in host if name == "window"]
    if not windows:
        raise ValueError("trace has no host span 'window'")
    lo, hi = windows[0]
    spans = [(s, s + d, name) for name, s, d, _ in host if name in SPANS]
    per_dev = {}
    op_s = collections.Counter()
    op_n = collections.Counter()
    op_dims = {}
    for plane, line, evs in lines:
        if not DEVICE_PLANE.match(plane) or line != OPS_LINE or not evs:
            continue
        iv, coll, loops = [], [], []
        for name, s, d, stats in evs:
            part = _clip([[s, s + d]], lo, hi)
            if not part:
                continue
            op = op_name(name)
            if CONTAINER.search(name.split(" = ", 1)[-1]):
                loops.append(part[0])
                continue
            op_s[op] += (part[0][1] - part[0][0]) * 1e-9
            op_n[op] += 1
            op_dims.setdefault(op, out_dims(name))
            (coll if COLLECTIVE.search(op) else iv).append(part[0])
        busy = _union(iv + coll + loops)
        compute, coll = _union(iv), _union(coll)
        per_dev[plane] = {
            "busy": busy,
            "busy_s": _length(busy) * 1e-9,
            "collective_s": _length(coll) * 1e-9,
            "collective_exposed_s": _length(_minus(coll, compute)) * 1e-9,
        }
    if not per_dev:
        raise ValueError("trace has no device op in the window")
    n = len(per_dev)
    gaps = collections.Counter()
    longest = []
    for dev in per_dev.values():
        for s, e in _minus([[lo, hi]], dev["busy"]):
            mid = (s + e) / 2
            open_ = [sp for sp in spans if sp[0] <= mid < sp[1]]
            label = max(open_)[2] if open_ else "host"
            gaps[label] += (e - s) * 1e-9 / n
            longest.append(((e - s) * 1e-9, label))
    longest.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev.values()) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"]
                                    for d in per_dev.values()) / n,
        "op_s": {k: v / n for k, v in op_s.items()},
        "op_n": {k: v / n for k, v in op_n.items()},
        "op_dims": op_dims,
        "idle_by_span_s": dict(gaps),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in op_s.most_common(TOP)],
            "idle_gaps": [[label, s] for s, label in longest[:TOP]],
        },
    }


def trace_file(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce_lines(load(trace_file(trace_dir)))
