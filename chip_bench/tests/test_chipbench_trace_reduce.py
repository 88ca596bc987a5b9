"""trace_reduce on hand-made event lines and on a small trace recorded
on a v5e chip (``data/v5e_tree_toy.xplane.pb``: two dispatches of the
tree engine at toy size inside a ``window`` span)."""

import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, end, **stats):
    return (name, float(start), float(end - start), stats)


LINES = [
    ("/host:CPU", "python", [ev("window", 0, 40), ev("engine", 0, 32),
                             ev("finalize", 32, 36)]),
    ("/device:TPU:0", "XLA Ops", [
        ev("%while.2 = (s32[]) while(s32[] %a), condition=%c, body=%b",
           0, 30),
        ev("%fusion.1 = f32[8,4]{1,0} fusion(f32[8] %p)", 0, 10),
        ev("%all-gather.3 = s32[4,512]{1,0} all-gather(s32[1,512] %x)",
           8, 14),
        ev("%fusion.22 = f32[2] fusion(f32[2] %q)", 20, 30),
        ev("%fusion.1 = f32[8,4]{1,0} fusion(f32[8] %p)", 38, 45)]),
    ("/device:TPU:0", "XLA Modules", [ev("jit_run", 0, 45)]),
]


def test_busy_idle_and_exposed_collectives():
    r = T.reduce_lines(LINES)
    assert r["window_s"] == pytest.approx(40e-9)
    # busy [0, 30) ∪ [38, 40): the while loop spans its body's gaps, and
    # ops are clipped to the window
    assert r["busy_s"] == pytest.approx(32e-9)
    assert r["collective_s"] == pytest.approx(6e-9)
    # the all-gather runs alone over [10, 14)
    assert r["collective_exposed_s"] == pytest.approx(4e-9)
    # the loop itself is no op of its own: its body's ops are
    assert "while.2" not in r["op_s"]
    assert r["op_s"]["fusion.1"] == pytest.approx(12e-9)
    assert r["op_n"] == {"fusion.1": 2, "all-gather.3": 1, "fusion.22": 1}
    assert r["op_dims"]["fusion.1"] == [8, 4]


def test_idle_gaps_take_the_host_span_open_at_their_middle():
    lines = [LINES[0], (LINES[1][0], LINES[1][1], LINES[1][2][1:])]
    r = T.reduce_lines(lines)
    # gaps [14, 20) inside engine, [30, 38) mid 34 inside finalize
    assert r["breakdown"]["idle_gaps"] == [
        ["finalize", pytest.approx(8e-9)], ["engine", pytest.approx(6e-9)]]
    assert r["idle_by_span_s"] == {"engine": pytest.approx(6e-9),
                                   "finalize": pytest.approx(8e-9)}


def test_devices_are_averaged():
    lines = LINES + [("/device:TPU:1", "XLA Ops", [ev("fusion.1", 0, 40)])]
    r = T.reduce_lines(lines)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((32e-9 + 40e-9) / 2)


def test_a_trace_without_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        T.reduce_lines(LINES[1:])
    with pytest.raises(ValueError):
        T.reduce_lines(LINES[:1])


def test_a_trace_recorded_on_the_chip():
    r = T.reduce_lines(T.load(os.path.join(DATA, "v5e_tree_toy.xplane.pb.gz")))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # one dispatch: the kernel once per tree level per round, N = 1, 2
    kernel = {k: r["op_n"][k] for k in r["op_s"]
              if T.base_name(k) == "hist_batched_pallas"}
    assert len(kernel) == 2 and len(set(kernel.values())) == 1
    assert sorted(r["op_dims"][k][-2] for k in kernel) == [2, 4]
    # every idle gap lies in one of the benchmark's spans
    assert set(r["idle_by_span_s"]) <= {"engine", "finalize"}
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert len(r["breakdown"]["device_ops"]) == T.TOP
