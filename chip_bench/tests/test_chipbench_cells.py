"""Cells at toy size on the CPU, in a copy of the benchmark.

The copy gets toy configurations, traffic mixes, cells and one new
per-layer metric as new files and new entries only; the harness must
find them by name.  Then: a sound run is correct; the controls (the
paper's round count halved; bfloat16 weights) and each fault planted
under the timed path (state returned unchanged, half the batch left
out, an answer altered where it is produced, a ledger that charges the
wrong bits) come out not correct or are read as the chip reads them.  ``run.py`` itself refuses the CPU; these tests drive
``harness.run_cell`` below it.
"""

import filecmp
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import harness

LIMITS = {"excess_errors": 0, "round_gap": 0, "ledger_gap": 0}
TOY_THR = {"class": "thresholds", "domain": 4096, "k": 4, "coreset": 32,
           "opt_budget": 16, "rounds_factor": 6,
           "deterministic_coreset": True, "rows_per_task": 512,
           "engine": "batched", "chips": 1, "limits": LIMITS}
TOY_CONFIGS = {
    "toy-thr": TOY_THR,
    "toy-tree": dict(TOY_THR, features=4, depth=2, bins=8,
                     comm_mode="histogram", deterministic_coreset=False,
                     **{"class": "tree"}),
}
TOY_TRAFFIC = {
    "toy-batch": {"driver": "closed_batch", "batch": 4,
                  "scenario": "targeted_heavy", "noise": 2, "pool": 2},
    "toy-xor": {"driver": "closed_batch", "batch": 2, "scenario": "xor",
                "noise": 2, "pool": 2},
}
TOY_CELLS = [("toy-thr-batch", "toy-thr", "toy-batch"),
             ("toy-tree-batch", "toy-tree", "toy-xor")]
CELLS = [c[0] for c in TOY_CELLS]
NEW_METRIC = '''"""toy_dispatches: dispatches in the window (a test metric)."""


def compute(records, trace):
    return float(len(records["dispatches"]))
'''
SEED = 2 ** 33 + 7


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    src = os.path.dirname(harness.BENCH_DIR)
    shutil.copytree(harness.BENCH_DIR, os.path.join(root, "chip_bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(os.path.join(root, "chip_bench"))
    bench = harness.load_json(os.path.join(src, "BENCHMARK.json"))
    for name, conf in TOY_CONFIGS.items():
        path = f"chip_bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(dict(conf, name=name), f)
        bench["configs"].append({"name": name, "source": "toy", "file": path,
                                 "reduced": [], "why": "toy"})
    for name, mix in TOY_TRAFFIC.items():
        with open(os.path.join(root, f"chip_bench/traffic/{name}.json"),
                  "w") as f:
            json.dump(mix, f)
    for name, conf, mix in TOY_CELLS:
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": mix, "chips": 1, "why": "toy"})
    batch = list(CELLS)
    for m in bench["end_to_end"]:
        if m["name"] == "tasks_per_s":
            m["workloads"] += batch
    bench["per_layer"].append({
        "name": "toy_dispatches", "unit": "dispatches", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "tasks_per_s", "workloads": batch})
    with open(os.path.join(root, "chip_bench/metrics/toy_dispatches.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digest(os.path.join(root, "chip_bench"))
    assert all(after[p] == h for p, h in before.items()), \
        "adding a cell edited a file the benchmark already had"
    return root


def run(root, cell, seconds=1.0, **kw):
    return harness.run_cell(root, cell, SEED, seconds, False,
                            time.perf_counter(), jax.devices()[:1],
                            os.path.join(root, "out"), **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_toy_cell_is_correct(toy_root, cell):
    out = run(toy_root, cell)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["side"]["compiles_in_window"] == 0


def test_new_cell_config_and_metric_found_by_name(toy_root):
    cell = harness.Cell.load(toy_root, "toy-thr-batch")
    assert cell.config["name"] == "toy-thr"
    assert cell.traffic == TOY_TRAFFIC["toy-batch"]
    assert "toy_dispatches" in [m["name"] for m in cell.per_layer()]
    records = {"dispatches": [{}, {}, {}]}
    got = harness.read_metrics(cell, records, None, per_layer=True)
    assert got["toy_dispatches"] == {"value": 3.0, "unit": "dispatches"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_halved_rounds_is_not_correct(toy_root, cell):
    out = run(toy_root, cell, program_overrides={"rounds_factor": 3})
    assert not out["correct"]
    assert out["compared"]["round_gap"]["value"] > 0


def test_bf16_control_is_judged_and_leaves_the_program_as_it_was(
        toy_root):
    import control
    from repro.core import weights
    from repro.kernels.histogram import kernel

    before = (kernel._hist_kernel, weights.erm_weights,
              weights.mixture_weights)
    with control.precision_control("bf16"):
        mix = jnp.asarray([1 / 3, 2 / 3], jnp.float32)
        got = weights.erm_weights(mix, 1)
        assert got.tolist() == mix.astype(jnp.bfloat16).astype(
            jnp.float32).tolist()
        out = run(toy_root, "toy-tree-batch")
    # judged as every run is; whether it passes is the chip's reading
    assert set(out["compared"]) == set(LIMITS) and out["attempted"] > 0
    assert (kernel._hist_kernel, weights.erm_weights,
            weights.mixture_weights) == before


def _unchanged(compiled, x, y, al, keys, sched, cfg, cls):
    from repro.core import batched
    return batched.init_state(x, y, keys, cfg, alive=al, cls=cls,
                              t_buf=cfg.num_rounds(x.shape[1] * x.shape[2]))


def _half(compiled, x, y, al, keys, sched, cfg, cls):
    out = compiled(x, y, al, keys, sched)
    init = _unchanged(compiled, x, y, al, keys, sched, cfg, cls)
    h = x.shape[0] // 2
    return jax.tree_util.tree_map(
        lambda o, i: jnp.concatenate([o[:h], i[h:]]), out, init)


def _altered(compiled, x, y, al, keys, sched, cfg, cls):
    out = compiled(x, y, al, keys, sched)
    # the last parameter is a sign in both classes (the threshold's, the
    # last leaf's)
    return out._replace(h_params=out.h_params.at[0, :, -1].multiply(-1.0))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_fault_under_the_batched_engine_is_not_correct(toy_root, fault,
                                                       cell, monkeypatch):
    from repro.core import batched

    real = batched.lower_classify

    def lower(x, y, alive, keys, cfg, cls, player_sched=None):
        compiled = real(x, y, alive, keys, cfg, cls)
        return lambda *a: fault(compiled, *a, cfg, cls)

    monkeypatch.setattr(batched, "lower_classify", lower)
    out = run(toy_root, cell)
    assert not out["correct"] and out["failed"] > 0


def test_a_ledger_one_bit_off_is_not_correct(toy_root, monkeypatch):
    from repro.core import batched

    real = batched.BatchedClassifyResult.ledger

    def ledger(self, b):
        led = real(self, b)
        led.bits_control += 1
        return led

    monkeypatch.setattr(batched.BatchedClassifyResult, "ledger", ledger)
    out = run(toy_root, "toy-tree-batch")
    assert not out["correct"]
    assert out["compared"]["ledger_gap"]["value"] == 1


def test_float32_hypothesis_bits_are_caught_by_the_ledger(toy_root,
                                                          monkeypatch):
    """What the v5e does to ``Thresholds.hypothesis_bits`` (one bit
    short on every broadcast) reads as a ledger gap of rounds · k."""
    from repro.core import weak

    real = weak.Thresholds.hypothesis_bits
    monkeypatch.setattr(weak.Thresholds, "hypothesis_bits",
                        lambda self: real(self) - 1)
    out = run(toy_root, "toy-thr-batch")
    assert not out["correct"]
    assert out["compared"]["ledger_gap"]["value"] > 0
    assert out["compared"]["excess_errors"]["value"] == 0


def test_run_refuses_the_cpu_and_a_bare_directory(tmp_path):
    src = os.path.dirname(harness.BENCH_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "chip_bench/run.py", "--workload",
           "tree28-batch-xor", "--seed", "1", "--seconds", "1"]
    r = subprocess.run(cmd, cwd=src, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), tmp_path)
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert filecmp.cmp(os.path.join(src, "BENCHMARK.json"),
                       tmp_path / "BENCHMARK.json")


def test_reference_imports_nothing_of_the_program():
    """The reference module imports nothing of the program."""
    import reference

    with open(reference.__file__) as f:
        text = f.read()
    assert "import repro" not in text and "from repro" not in text
