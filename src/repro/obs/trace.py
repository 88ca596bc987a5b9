"""Host-side span/event tracing in Chrome trace-event format.

A :class:`TraceRecorder` collects *complete* events (``ph: "X"`` —
named spans with microsecond ``ts``/``dur``) and *instant* events
(``ph: "i"``), the subset of the Chrome trace-event spec that Perfetto
and ``chrome://tracing`` render natively.  Load the JSON written by
:meth:`TraceRecorder.save` straight into https://ui.perfetto.dev.

Span taxonomy (docs/observability.md): ``attempt``, ``round``,
``run_rounds``, ``finalize``, ``quarantine``, ``compile``, ``dispatch``,
``preempt``, ``resume``, ``ckpt_save`` / ``ckpt_restore``.  Round and
attempt spans carry a ``task_bits`` args dict — per-task wire bits by
ledger category — which :func:`repro.obs.roundtrace.validate_trace`
proves bit-exact against the Theorem 4.1 ledger.

Tracing is **disabled by default**.  The module-level :func:`span` /
:func:`instant` helpers return a preallocated no-op when no recorder is
active, so the instrumented hot paths pay one ``is None`` test — the
benchmarks/observability.py overhead gate holds this under 2% on the
batched engine.

Device-side nesting: :func:`annotate` wraps
``jax.profiler.TraceAnnotation`` so, when a profiler trace is being
captured (:func:`device_trace`), device activity appears under the
host protocol spans.  Emission from *inside* jitted code is a lint
error (RL006) — a traced obs call would run once at trace time and
never again.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax


# Ledger-category ↔ Ledger-field mapping: the ``task_bits`` dicts that
# round/attempt spans carry are keyed by these categories, and
# repro.obs.roundtrace.validate_trace compares their sums field-by-field
# against the Theorem 4.1 Ledger (docs/observability.md has the table).
CATEGORY_FIELDS = {
    "coreset": "bits_coresets",
    "ws": "bits_weight_sums",
    "hypotheses": "bits_hypotheses",
    "control": "bits_control",
    "histograms": "bits_histograms",
    "votes": "bits_votes",
    "quarantine": "bits_dispute",
}


def ledger_bits(led) -> dict:
    """A ``repro.core.types.Ledger`` (or delta of one) as a per-category
    bits dict — the span ``task_bits`` payload format."""
    return {cat: int(getattr(led, field))
            for cat, field in CATEGORY_FIELDS.items()}


class _NullSpan:
    """Shared no-op span: the disabled-tracing fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def update(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Span:
    """One complete event; a context manager timing its ``with`` body.

    ``update(**args)`` merges into the event's args — callable after
    the timed work so spans can carry results (round counts, wire
    bits) computed inside the region.
    """

    __slots__ = ("_rec", "name", "cat", "args", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 args: dict):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0

    def update(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "Span":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._rec._complete(self.name, self.cat, self._t0,
                            time.perf_counter(), self.args)
        return False


class TraceRecorder:
    """Append-only event sink (thread-safe: list.append is atomic).

    ``ts`` is microseconds since the recorder's construction — a fresh
    recorder after checkpoint/resume restarts the clock, which Perfetto
    renders fine and the ledger validator ignores (it sums ``args``
    payloads, never timestamps).
    """

    def __init__(self):
        self.events: list[dict] = []
        self._epoch = time.perf_counter()
        self._pid = os.getpid()

    # -- emission -----------------------------------------------------------

    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def _complete(self, name: str, cat: str, t0: float, t1: float,
                  args: dict) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": self._us(t0), "dur": max(self._us(t1) - self._us(t0), 0.0),
            "pid": self._pid, "tid": threading.get_ident(),
            "args": args})

    def span(self, name: str, cat: str = "protocol", **args) -> Span:
        return Span(self, name, cat, dict(args))

    def instant(self, name: str, cat: str = "protocol", **args) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._us(time.perf_counter()),
            "pid": self._pid, "tid": threading.get_ident(),
            "args": dict(args)})

    # -- export -------------------------------------------------------------

    def extend(self, events) -> None:
        """Merge events from another recorder (e.g. the pre-preemption
        segment of a resumed run) — validation spans both segments."""
        self.events.extend(events)

    def chrome_trace(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        """Write Perfetto-loadable JSON (atomic: tmp + rename)."""
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)


# ---------------------------------------------------------------------------
# module-level switchboard: the instrumentation sites call these
# ---------------------------------------------------------------------------

_ACTIVE: TraceRecorder | None = None


def enable(recorder: TraceRecorder | None = None) -> TraceRecorder:
    """Install (and return) the active recorder; idempotent-friendly —
    pass an existing recorder to keep appending to it."""
    global _ACTIVE
    _ACTIVE = recorder if recorder is not None else TraceRecorder()
    return _ACTIVE


def disable() -> TraceRecorder | None:
    """Deactivate tracing; returns the recorder that was active."""
    global _ACTIVE
    rec, _ACTIVE = _ACTIVE, None
    return rec


def active() -> TraceRecorder | None:
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


@contextlib.contextmanager
def recording(recorder: TraceRecorder | None = None):
    """Scoped enable/disable; yields the recorder."""
    rec = enable(recorder)
    try:
        yield rec
    finally:
        if _ACTIVE is rec:
            disable()


def span(name: str, cat: str = "protocol", **args):
    """A timing span when tracing is on, the shared no-op when off."""
    rec = _ACTIVE
    if rec is None:
        return _NULL_SPAN
    return rec.span(name, cat, **args)


def instant(name: str, cat: str = "protocol", **args) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.instant(name, cat, **args)


def annotate(name: str):
    """``jax.profiler.TraceAnnotation`` under an active recorder —
    nests device activity (when a profiler trace is being captured)
    under the host protocol span of the same region; a no-op context
    otherwise."""
    if _ACTIVE is None:
        return _NULL_SPAN
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a ``jax.profiler`` device trace alongside host spans —
    open the resulting directory in TensorBoard/Perfetto and the
    :func:`annotate` regions frame the device activity."""
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
