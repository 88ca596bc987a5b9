"""Each metric's arithmetic on fixed records and a fixed reduced trace."""

import os

import pytest

import harness


def metric(folder, name):
    return harness.load_module(os.path.join(harness.BENCH_DIR, folder,
                                            name + ".py")).compute


BATCH = {
    "t0": 10.0, "close": 30.0, "batch": 8, "setup_s": 41.5,
    "dispatches": [
        {"start": 10.0, "end": 15.0},
        {"start": 15.0, "end": 20.0},
        {"start": 20.0, "end": 32.0},          # done after the close
    ],
    "config": {"k": 16, "depth": 2, "coreset": 512, "features": 28,
               "bins": 32},
    "traffic": {"batch": 8}, "device_kind": "TPU v5 lite",
}
TRACE = {"window_s": 20.0, "busy_s": 19.0,
         "op_s": {"hist_batched_pallas.24": 0.45,
                  "hist_batched_pallas.25": 0.5, "fusion.3": 17.0},
         "op_n": {"hist_batched_pallas.24": 500.0,
                  "hist_batched_pallas.25": 500.0, "fusion.3": 9.0},
         "op_dims": {"hist_batched_pallas.24": [8, 16, 1, 2, 1024],
                     "hist_batched_pallas.25": [8, 16, 1, 4, 1024],
                     "fusion.3": [8]}}


def test_setup_s_is_the_recorded_one():
    assert metric("end_to_end", "setup_s")(BATCH, None) == 41.5


def test_tasks_per_s_counts_dispatches_done_in_the_window():
    # two dispatches of 8 done by t = 20, from t0 = 10
    assert metric("end_to_end", "tasks_per_s")(BATCH, None) == 16 / 10


def test_tasks_per_s_is_silent_without_a_dispatch_done_in_the_window():
    records = dict(BATCH, dispatches=BATCH["dispatches"][2:])
    assert metric("end_to_end", "tasks_per_s")(records, None) is None


def test_idle_share():
    assert metric("metrics", "device_idle_share.batch")(
        BATCH, TRACE) == pytest.approx(5.0)


def test_histogram_kernel_shares():
    assert metric("metrics", "hist_kernel_busy_share")(
        BATCH, TRACE) == pytest.approx(100 * 0.95 / 19)
    # 500 launches at N = 1 and 500 at N = 2, each over 8·16 (task,
    # party) pairs, bytes-bound
    per_round = 128 * 4 * ((14336 + 1024 + 1792) + (14336 + 2048 + 3584))
    want = 100 * 500 * per_round / 819e9 / 0.95
    assert metric("metrics", "histogram_roofline")(
        BATCH, TRACE) == pytest.approx(want)


def test_roofline_of_feature_blocked_launches():
    # 256 bins: 28 features in 4 blocks of 8 (32 padded), one task of
    # 16 parties, N = 2; the padding counts for nothing
    trace = dict(TRACE, op_s={"hist_batched_pallas.7": 0.1},
                 op_n={"hist_batched_pallas.7": 100.0},
                 op_dims={"hist_batched_pallas.7": [1, 16, 4, 4, 2048]})
    records = dict(BATCH, config=dict(BATCH["config"], bins=256))
    nbytes = 16 * 4 * (512 * 28 + 2 * 2 * 512 + 2 * 2 * 28 * 256)
    want = 100 * 100 * nbytes / 819e9 / 0.1
    assert metric("metrics", "histogram_roofline")(
        records, trace) == pytest.approx(want)


def test_kernel_metrics_are_silent_without_launches():
    trace = dict(TRACE, op_s={"fusion.3": 17.0})
    assert metric("metrics", "hist_kernel_busy_share")(BATCH, trace) is None
    assert metric("metrics", "histogram_roofline")(BATCH, trace) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        metric("metrics", "histogram_roofline")(
            dict(BATCH, device_kind="TPU v9"), TRACE)
