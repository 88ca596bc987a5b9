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
active and no profiler capture records, so the instrumented hot paths
pay one ``is None`` test and one capture check — the
benchmarks/observability.py overhead gate holds this under 2% on the
batched engine.

The device trace's clock: while a ``jax.profiler`` capture is
recording, every :func:`span` also opens a
``jax.profiler.TraceAnnotation`` of its name, recorder or not, so the
host spans land in the capture's ``.xplane.pb`` beside the device ops.
Emission from *inside* jitted code is a lint error (RL006) — a traced
obs call would run once at trace time and never again.

Device ops by protocol step: the round body names each step with a
``jax.named_scope`` from :data:`ROUND_STEPS`; the compiler keeps the
scope in every instruction's ``op_name``.  The engines'
``lower_classify*`` publish the executables they return
(:func:`publish_program`, held weakly), and :func:`op_steps` maps
each instruction name of their optimized HLO to its step — the names
a device trace gives its ops.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import threading
import time
import weakref

import jax
from jax._src.lib import _profiler

from repro.obs import metrics

# True only while a profiler capture is recording (≈ 40 ns a call)
_capturing = _profiler.TraceMe.is_enabled

# The protocol steps of one wire round, each a ``jax.named_scope`` in
# the round body (core/boost_attempt.py, core/batched.py,
# core/sharded_batched.py); docs/observability.md maps each to its code.
ROUND_STEPS = ("sort_order", "coreset_draw", "weight_sums", "center_erm",
               "predict", "mw_update", "lane_state", "quarantine")


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


class _ProfilerSpan:
    """A span on the profiler's clock alone: a capture is recording and
    no recorder is installed."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def update(self, **args) -> None:
        pass


class Span:
    """One complete event; a context manager timing its ``with`` body.

    ``update(**args)`` merges into the event's args — callable after
    the timed work so spans can carry results (round counts, wire
    bits) computed inside the region.
    """

    __slots__ = ("_rec", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 args: dict):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._ann = None

    def update(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "Span":
        if _capturing():
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._rec._complete(self.name, self.cat, self._t0,
                            time.perf_counter(), self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
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
    """A timing span when a recorder is installed; on the profiler's
    clock too while a capture is recording; else the shared no-op."""
    rec = _ACTIVE
    if rec is None:
        if _capturing():
            return _ProfilerSpan(name)
        return _NULL_SPAN
    return rec.span(name, cat, **args)


def instant(name: str, cat: str = "protocol", **args) -> None:
    rec = _ACTIVE
    if rec is not None:
        rec.instant(name, cat, **args)


# ---------------------------------------------------------------------------
# device ops by protocol step: published programs and their optimized HLO
# ---------------------------------------------------------------------------

# an instruction line: ``[ROOT ]%name = <shape> <opcode>(<operands>), …``
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
# a computation's first line: ``[ENTRY ]%name (<params>) -> <shape> {``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation"
    r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
# a name-stack component wrapped by a transform: ``vmap(center_erm)``
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def _scope_step(op_name: str) -> str | None:
    """The innermost component of an ``op_name`` that is a step."""
    for part in reversed(op_name.split("/")):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in ROUND_STEPS:
            return part
    return None


def _operands(rest: str, op) -> list:
    """The instruction names inside the opcode's parentheses."""
    if op is None:
        return []
    depth, end = 1, op.end()
    while end < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[end], 0)
        end += 1
    return _OPERAND.findall(rest, op.end(), end)


def parse_hlo(text: str) -> dict:
    """{computation: [(instruction, opcode, step of its own op_name or
    None, [computations it calls], [its operands])]} of an HLO module's
    text, each computation's instructions in program order."""
    comps: dict = {}
    body = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is not None and body is not None:
            rest = m.group(2)
            op = _OPCODE.search(rest)
            name = _OP_NAME.search(rest)
            called = _CALLED.findall(rest)
            for group in _BRANCHES.findall(rest):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            body.append((m.group(1), op.group(1) if op else "",
                         _scope_step(name.group(1)) if name else None,
                         called, _operands(rest, op)))
            continue
        m = _COMPUTATION.match(line)
        if m is not None:
            body = comps.setdefault(m.group(1), [])
    return comps


def _majority(steps) -> str | None:
    """The most frequent step (ties: the earlier of ROUND_STEPS)."""
    count = collections.Counter(s for s in steps if s is not None)
    if not count:
        return None
    return max(count, key=lambda s: (count[s], -ROUND_STEPS.index(s)))


def hlo_steps(text: str) -> dict:
    """{instruction name: step or None} of an HLO module's text.

    An instruction's step is the innermost step of its ``op_name``.
    One with none takes the majority step of the instructions in the
    computations it calls (a fusion's fused computation).  One still
    with none — mostly a copy the compiler inserted — takes the majority
    step of its users in its computation, else that of its operands
    (resolved first: the text is in program order).  Else it is
    unscoped (None).  Ties go to the earlier step of
    :data:`ROUND_STEPS`.
    """
    comps = parse_hlo(text)
    votes: dict = {}

    def called_step(called) -> str | None:
        return _majority(s for c in called for s in comp_steps(c))

    def comp_steps(c) -> list:
        if c not in votes:
            votes[c] = []                       # a cycle reads empty
            votes[c] = [step or called_step(called)
                        for _, _, step, called, _ in comps.get(c, ())]
        return votes[c]

    out = {}
    for c, instrs in comps.items():
        own = dict(zip((i[0] for i in instrs), comp_steps(c)))
        users = collections.defaultdict(list)
        for name, *_, operands in instrs:
            for o in operands:
                users[o].append(own.get(name))
        for name, *_, operands in instrs:
            out[name] = (own[name] or _majority(users[name])
                         or _majority(out.get(o) for o in operands))
    return out


class _Program:
    """A published executable's program, held only while its owner
    holds the executable, and its instruction → step map once built."""

    __slots__ = ("program", "steps")

    def __init__(self, program):
        self.program = program
        self.steps = None

    def build(self) -> dict:
        if self.steps is None:
            self.steps = hlo_steps(self.program.as_text() or "")
        return self.steps


_PUBLISHED: list = []
# the maps of dropped programs, built as each was dropped (the newest)
_DROPPED: collections.deque = collections.deque(maxlen=16)


def publish_program(compiled) -> None:
    """Publish a ``jax.stages.Compiled`` for :func:`op_steps`, weakly:
    the caller still owns it, and dropping it frees the program (a
    compile-cache eviction relies on that).  Its map is built when
    first read, or as it is dropped, whichever comes first."""
    prog = _Program(compiled._executable)
    _PUBLISHED.append(prog)
    weakref.finalize(compiled, _dropped, prog).atexit = False


def _dropped(prog: _Program) -> None:
    try:
        _DROPPED.append(prog.build())
    finally:
        prog.program = None
        if prog in _PUBLISHED:
            _PUBLISHED.remove(prog)


def op_steps() -> dict:
    """{HLO instruction name: protocol step} over every published
    program, built on first read (never while a program runs).  An
    instruction with no step, or one that two programs map differently,
    is left out: a device op of that name is unscoped."""
    merged: dict = {}
    clash: set = set()
    for steps in [p.build() for p in list(_PUBLISHED)] + list(_DROPPED):
        for name, step in steps.items():
            if merged.setdefault(name, step) != step:
                clash.add(name)
    return {name: step for name, step in merged.items()
            if step is not None and name not in clash}


def compile_published(jitted, *args):
    """``jitted.lower(*args).compile()``, counted and published.

    The seconds of ``.lower()`` (tracing and lowering, which the
    persistent compile cache does not serve) and of ``.compile()`` (the
    backend compile, or its load from that cache) are added to the
    default registry's ``compile.lower_s`` / ``compile.backend_s``
    counters, ``compile.programs`` counts the program, and the
    executable is published for :func:`op_steps`."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    reg = metrics.default_registry()
    reg.counter("compile.lower_s").inc(t1 - t0)
    reg.counter("compile.backend_s").inc(t2 - t1)
    reg.counter("compile.programs").inc()
    publish_program(compiled)
    return compiled
