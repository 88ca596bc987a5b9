"""Closed loop of whole dispatches: batch tenants.

One client keeps one dispatch of ``batch`` tasks in flight on the
batched engine: when a dispatch returns (ensemble, quarantine masks and
histories on the host: the engine's ``finalize``), the next starts,
over a pool of ``pool`` distinct batches made from the seed in set-up
and dispatched in turn.

Parameters (``traffic/<mix>.json``): ``batch``, ``scenario``, ``noise``,
``pool``.  A task has the configuration's ``rows_per_task`` rows.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import harness
import taskgen


def setup(ctx) -> dict:
    from repro.core import batched

    config, traffic = ctx.cell.config, ctx.cell.traffic
    cls, cfg = harness.program_objects(config)
    B, m, k = traffic["batch"], config["rows_per_task"], config["k"]
    device = ctx.devices[0]
    staged = []
    pool = []
    for i in range(traffic["pool"]):
        x, y = taskgen.make_batch(config, ctx.seed, i, B, m,
                                  traffic["scenario"], traffic["noise"])
        word = int(taskgen.seed_rng(ctx.seed, i, 0x6B).integers(2 ** 31))
        keys = jax.random.split(jax.random.key(word), B)
        pool.append((x, y))
        staged.append(tuple(jax.device_put(a, device) for a in (
            x, y, np.ones((B, k, m // k), bool), keys)))
    sched = jax.device_put(batched.canon_player_sched(None, B, k), device)
    compiled = batched.lower_classify(*staged[0], cfg, cls)

    def run(i):
        with harness.span("stage"):
            x, y, alive, keys = staged[i % len(staged)]
        with harness.span("engine"):
            out = compiled(x, y, alive, keys, sched)
            jax.block_until_ready(out)
        with harness.span("finalize"):
            return batched.finalize(out, x, y, alive, cfg, cls)

    run(0)                                   # the warm-up dispatch
    return {"run": run, "pool": pool, "B": B}


def window(ctx, st) -> dict:
    """Dispatch until ``seconds`` have passed; the dispatch in flight at
    the close runs to its end and is checked, but not counted."""
    t0 = time.perf_counter()
    close = t0 + ctx.seconds
    dispatches = []
    i = 0
    while time.perf_counter() < close:
        start = time.perf_counter()
        res = st["run"](i)
        dispatches.append({"pool": i % len(st["pool"]), "start": start,
                           "end": time.perf_counter(), "result": res})
        i += 1
    return {"kind": "closed_batch", "t0": t0, "close": close,
            "batch": st["B"], "dispatches": dispatches}


def lanes(ctx, st, records) -> list:
    """(x, y, real rows, lane) of every task of every dispatch."""
    out = []
    for d in records["dispatches"]:
        x, y = st["pool"][d["pool"]]
        res = d.pop("result")
        for b in range(st["B"]):
            out.append((x[b], y[b], np.ones(y[b].shape, bool),
                        harness.lane_of(res, b)))
    return out


def summary(records) -> dict:
    ds = records["dispatches"]
    return {"dispatches": len(ds),
            "in_window": sum(d["end"] <= records["close"] for d in ds),
            "dispatch_s": [round(d["end"] - d["start"], 4) for d in ds]}
