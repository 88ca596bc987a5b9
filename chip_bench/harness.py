"""Runs one cell of ``BENCHMARK.json`` once: set-up, the timed window,
the comparison with the plain reference, and the result line.

Everything is found by name.  A cell names a configuration
(``configs/<file>.json``, via ``BENCHMARK.json``) and a traffic mix
(``traffic/<mix>.json``); the mix names the driver that plays it
(``drivers/<driver>.py``: ``setup``, ``window``, ``lanes``,
``summary``).  Each end-to-end metric is ``end_to_end/<name>.py`` and
each per-layer metric ``metrics/<name>.py``, both with
``compute(records, trace)``; the benchmark's entries say which cells
report which.  A new cell, mix, configuration or metric is new files
and new entries, never an edit.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import reference  # noqa: E402  (the benchmark's own module)
import trace_reduce  # noqa: E402


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path (its name may hold
    dots, as a metric's does)."""
    name = "chip_bench_" + os.path.abspath(path).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A cell with everything it names, read from the files."""

    root: str
    bench: dict
    entry: dict
    config: dict
    traffic: dict

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"no cell {name!r} in BENCHMARK.json; cells: "
                             f"{sorted(by_name)}")
        entry = by_name[name]
        conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        config = load_json(os.path.join(root, conf["file"]))
        traffic = load_json(os.path.join(
            root, bench["paths"][0], "traffic", entry["traffic"] + ".json"))
        return cls(root, bench, entry, config, traffic)

    @property
    def name(self) -> str:
        return self.entry["name"]

    def file(self, *parts: str) -> str:
        """A file of the benchmark that holds this cell."""
        return os.path.join(self.root, self.bench["paths"][0], *parts)

    def driver(self):
        return load_module(self.file("drivers",
                                     self.traffic["driver"] + ".py"))

    def _reports(self, metric: dict) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return True

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        moved = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if m["moves"] in moved and self._reports(m)]


class Compiles:
    """Counts JAX compilations (seconds and events) by phase, from the
    ``/jax/core/compile/*`` duration events."""

    def __init__(self):
        self.phase = "setup"
        self.events = collections.Counter()
        self.seconds = collections.Counter()

    def __call__(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events[self.phase] += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds[self.phase] += secs


def span(name: str):
    """A host span on the profiler's clock (a no-op when no trace is
    being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Context:
    """What a driver is handed: the cell, the run's seed and length, and
    the devices it runs on."""

    cell: Cell
    seed: int
    seconds: float
    devices: list


def program_objects(config: dict):
    """(hypothesis class, BoostConfig) of the program for a
    configuration's file."""
    from repro.core import weak
    from repro.core.types import BoostConfig

    cls = weak.make_class(
        config["class"], n=config.get("domain", 0),
        num_features=config.get("features", 0),
        tree_depth=config.get("depth", 2), tree_bins=config.get("bins", 32),
        tree_comm_mode=config.get("comm_mode", "coreset"))
    cfg = BoostConfig(
        k=config["k"], coreset_size=config["coreset"],
        domain_size=config["domain"], opt_budget=config["opt_budget"],
        rounds_factor=config["rounds_factor"],
        deterministic_coreset=config["deterministic_coreset"])
    return cls, cfg


def lane_of(result, b: int) -> dict:
    """What the timed path returned for lane b of a dispatch, with the
    ledger the service reports for it."""
    return {
        "ok": bool(result.ok[b]),
        "hypotheses": result.hypotheses[b], "rounds": int(result.rounds[b]),
        "disputed": result.disputed[b], "attempts": int(result.attempts[b]),
        "hist_rounds": result.hist_rounds[b],
        "hist_stuck": result.hist_stuck[b],
        "hist_alive": result.hist_alive[b], "hist_p": result.hist_p[b],
        "ledger_bits": int(result.ledger(b).total_bits),
    }


def judge(config: dict, tasks: list) -> tuple[dict, int]:
    """Worst reading of each compared number over the tasks, and the
    number of tasks that failed (never returned, not ok, or over a
    limit).  ``tasks`` holds (x, y, real, lane-or-None)."""
    limits = config["limits"]
    worst = {name: None for name in limits}
    failed = 0
    for i, (x, y, real, lane) in enumerate(tasks):
        if lane is None or not lane["ok"]:
            failed += 1
            _report(i, lane, None)
            continue
        nums = reference.task_numbers(config, x, y, real, lane)
        bad = False
        for name, v in nums.items():
            if name in worst:
                worst[name] = v if worst[name] is None else max(worst[name], v)
                bad |= v > limits[name]
        if bad and not failed:
            _report(i, lane, nums)
        failed += bad
    return {n: v for n, v in worst.items() if v is not None}, failed


def _report(i: int, lane, nums) -> None:
    """What the first failed task returned, on standard error."""
    keep = ("ok", "rounds", "attempts")
    hist = ("hist_rounds", "hist_stuck", "hist_alive", "hist_p")
    info = {"task": i, "numbers": nums}
    if lane is not None:
        n = lane["attempts"]
        info.update({k: lane[k] for k in keep})
        info.update({k: np.asarray(lane[k])[:n].tolist() for k in hist})
    print("failed task " + json.dumps(info, default=int), file=sys.stderr)


def read_metrics(cell: Cell, records: dict, reduced, per_layer: bool):
    """The cell's per-layer metrics (from the window's records and the
    reduced trace) or its end-to-end ones; a reader that finds nothing
    to read returns None and its metric is left out."""
    wanted = cell.per_layer() if per_layer else cell.end_to_end()
    folder = "metrics" if per_layer else "end_to_end"
    metrics = {}
    for m in wanted:
        mod = load_module(cell.file(folder, m["name"] + ".py"))
        value = mod.compute(records, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices, out_dir: str,
             program_overrides: dict | None = None) -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``program_overrides`` replaces keys of the configuration the
    program is built from (the control runs the program so), never the
    limits or what the reference reads."""
    import jax

    cell = Cell.load(root, name)
    cell.config = dict(cell.config, **(program_overrides or {}))
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        ctx = Context(cell, seed, seconds, devices)
        driver = cell.driver()
        state = driver.setup(ctx)
        trace_dir = os.path.join(out_dir, "trace", f"{name}_{seed}")
        if trace:
            jax.profiler.start_trace(trace_dir)
        compiles.phase = "window"
        setup_s = time.perf_counter() - t_start
        with span("window"):
            records = driver.window(ctx, state)
        if trace:
            jax.profiler.stop_trace()
        compiles.phase = "check"
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    records.update(setup_s=setup_s, config=cell.config,
                   traffic=cell.traffic, device_kind=devices[0].device_kind)
    records["compiles_in_window"] = compiles.events["window"]
    memory = peak_memory(devices)
    tasks = driver.lanes(ctx, state, records)
    del state
    compared, failed = judge(cell.config, tasks)
    reduced = None
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir)        # read once; traces are large
    metrics = read_metrics(cell, records, reduced, per_layer=trace)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    out = {"correct": failed == 0 and bool(tasks), "attempted": len(tasks),
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["side"] = {"setup_compile_s": compiles.seconds["setup"],
                   "compiles_in_window": compiles.events["window"],
                   "window_records": driver.summary(records)}
    limits = cell.config["limits"]
    out["compared"] = {n: {"value": v, "limit": limits[n]}
                       for n, v in compared.items()}
    return out
