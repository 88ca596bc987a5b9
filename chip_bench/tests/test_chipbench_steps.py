"""The readers of what the program publishes about itself: device op
time by protocol step (``step_share.*``) and the set-up counters
(``setup_lower_s``, ``setup_compile_s``), on a fixed reduced trace and
a hand-made map."""

import os

import pytest

import harness
import steps


def metric(name):
    return harness.load_module(os.path.join(harness.BENCH_DIR, "metrics",
                                            name + ".py")).compute


RECORDS = {"t0": 10.0, "close": 30.0, "batch": 8, "setup_s": 41.5,
           "dispatches": [{"start": 10.0, "end": 15.0}],
           "device_kind": "TPU v5 lite"}
TRACE = {"window_s": 20.0, "busy_s": 19.0,
         "op_s": {"hist_batched_pallas.24": 0.45,
                  "hist_batched_pallas.25": 0.5, "fusion.3": 17.0},
         "op_n": {"hist_batched_pallas.24": 500.0,
                  "hist_batched_pallas.25": 500.0, "fusion.3": 9.0}}
STEPS = ("coreset_draw", "weight_sums", "center_erm", "predict",
         "mw_update", "lane_state", "quarantine", "unscoped")
# the program's map of the ops of TRACE; fusion.9 is no op of it
OP_STEPS = {"hist_batched_pallas.24": "center_erm",
            "hist_batched_pallas.25": "center_erm", "fusion.9": "predict"}


def test_step_shares_sum_to_100(monkeypatch):
    monkeypatch.setattr(steps, "op_steps", lambda: OP_STEPS)
    got = {s: metric("step_share." + s)(RECORDS, TRACE) for s in STEPS}
    total = 17.95
    assert got["center_erm"] == pytest.approx(100 * 0.95 / total)
    assert got["unscoped"] == pytest.approx(100 * 17.0 / total)
    assert got["predict"] == 0.0
    assert sum(got.values()) == pytest.approx(100.0)


def test_step_shares_are_silent_without_a_map(monkeypatch):
    monkeypatch.setattr(steps, "op_steps", lambda: {})
    for s in STEPS:
        assert metric("step_share." + s)(RECORDS, TRACE) is None


def test_setup_counters_read_the_program_registry(monkeypatch):
    from repro.obs import metrics as M
    reg = M.MetricsRegistry()
    monkeypatch.setattr(M, "default_registry", lambda: reg)
    assert metric("setup_lower_s")(RECORDS, TRACE) is None
    assert metric("setup_compile_s")(RECORDS, TRACE) is None
    assert "compile.lower_s" not in reg.names()     # read, not created
    reg.counter("compile.lower_s").inc(3.25)
    reg.counter("compile.backend_s").inc(11.5)
    assert metric("setup_lower_s")(RECORDS, TRACE) == 3.25
    assert metric("setup_compile_s")(RECORDS, TRACE) == 11.5
