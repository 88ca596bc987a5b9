"""The Epsilon-width tree cell (``tree2k-batch-xor-b1``): found by name,
run at its widths (2,000 features on 256 bins) with toy parties,
coresets and rows on the CPU, in a copy of the benchmark; and the
readers of the parts of the center's ERM (``erm_part_share.*``) on a
fixed reduced trace and a hand-made map."""

import json
import os
import shutil
import time

import jax
import pytest

import erm_parts
import harness

ROOT = os.path.dirname(harness.BENCH_DIR)
CELL = "tree2k-batch-xor-b1"
SEED = 2 ** 33 + 11
# the cell's configuration with toy parties, coresets and rows: every
# width (features, bins, depth, comm_mode) as the cell runs it
TOY = {"k": 4, "coreset": 32, "opt_budget": 32, "rows_per_task": 1024}


def test_cell_is_found_by_name():
    cell = harness.Cell.load(ROOT, CELL)
    assert cell.entry["config"] == cell.config["name"] == "trees-f2k-q256"
    assert (cell.config["features"], cell.config["bins"],
            cell.config["depth"], cell.config["comm_mode"]) == (
                2000, 256, 2, "histogram")
    assert cell.config["reduced_from"]["rows_per_task"] == 400000
    assert cell.traffic == {"driver": "closed_batch", "batch": 1,
                            "scenario": "xor", "noise": 2, "pool": 4}
    assert cell.entry["chips"] == 1
    names = {m["name"] for m in cell.per_layer()}
    assert {"erm_part_share.hist_merge", "erm_part_share.split_search",
            "hist_kernel_busy_share", "histogram_roofline"} <= names
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s",
                                                      "tasks_per_s"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark whose configuration file for the cell
    holds the toy sizes, and whose pool is one batch."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(harness.BENCH_DIR, os.path.join(root, "chip_bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cell = harness.Cell.load(root, CELL)
    path = os.path.join(root, "chip_bench", "configs",
                        cell.config["name"] + ".json")
    with open(path, "w") as f:
        json.dump(dict(cell.config, **TOY), f)
    with open(cell.file("traffic", cell.entry["traffic"] + ".json"),
              "w") as f:
        json.dump(dict(cell.traffic, pool=1), f)
    return root


def test_toy_wide_cell_is_correct(toy_root):
    # a window of a millisecond: the one dispatch in flight at its close
    out = harness.run_cell(toy_root, CELL, SEED, 1e-3, False,
                           time.perf_counter(), jax.devices()[:1],
                           os.path.join(toy_root, "out"))
    assert out["correct"], out
    assert out["attempted"] == 1
    assert out["compared"]["ledger_gap"]["value"] == 0
    assert out["compared"]["round_gap"]["value"] == 0
    assert out["side"]["compiles_in_window"] == 0


def metric(name):
    return harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                            name + ".py")).compute


RECORDS = {"t0": 10.0, "close": 30.0, "batch": 1, "setup_s": 41.5,
           "dispatches": [{"start": 10.0, "end": 15.0}],
           "device_kind": "TPU v5 lite"}
TRACE = {"window_s": 20.0, "busy_s": 19.0,
         "op_s": {"hist_batched_pallas.24": 16.0, "reduce.7": 0.5,
                  "fusion.3": 1.25, "fusion.4": 0.25},
         "op_n": {"hist_batched_pallas.24": 500.0, "reduce.7": 500.0,
                  "fusion.3": 500.0, "fusion.4": 9.0}}
# the program's map of the ops of TRACE: the kernel and fusion.4 are in
# no part; copy.9 is no op of the trace
OP_PARTS = {"reduce.7": "hist_merge", "fusion.3": "split_search",
            "copy.9": "hist_merge"}


def test_erm_part_shares_read_the_program_map(monkeypatch):
    monkeypatch.setattr(erm_parts, "op_parts", lambda: OP_PARTS)
    total = 18.0
    assert metric("erm_part_share.hist_merge")(RECORDS, TRACE) == \
        pytest.approx(100 * 0.5 / total)
    assert metric("erm_part_share.split_search")(RECORDS, TRACE) == \
        pytest.approx(100 * 1.25 / total)


@pytest.mark.parametrize("part", ["hist_merge", "split_search"])
def test_erm_part_shares_are_silent_without_a_map(monkeypatch, part):
    monkeypatch.setattr(erm_parts, "op_parts", lambda: {})
    assert metric("erm_part_share." + part)(RECORDS, TRACE) is None


def test_erm_parts_read_nothing_from_a_program_without_them(monkeypatch):
    """The parent's program has ``op_steps`` and no ``op_parts``."""
    from repro.obs import trace

    monkeypatch.delattr(trace, "op_parts")
    assert erm_parts.op_parts() == {}
    assert metric("erm_part_share.hist_merge")(RECORDS, TRACE) is None
