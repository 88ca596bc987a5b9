"""roofline/histogram.py against shapes counted by hand."""

import os

import harness

H = harness.load_module(os.path.join(harness.BENCH_DIR, "roofline",
                                     "histogram.py"))


def test_one_launch_counted_by_hand():
    # one (task, party) pair, N = 2 nodes, c = 512 rows, F = 28, Q = 32:
    # adds 2·2·512·28 = 57344; bytes 4·(512·28 + 2·2·512 + 2·2·28·32)
    ops, nbytes = H.launch(1, 2, 512, 28, 32)
    assert ops == 57344
    assert nbytes == 4 * (14336 + 2048 + 3584) == 79872


def test_units_scale_both_counts():
    one = H.launch(1, 1, 512, 28, 32)
    assert H.launch(128, 1, 512, 28, 32) == (128 * one[0], 128 * one[1])


def test_units_and_nodes_from_the_launch_result():
    # batched engine: [tasks, parties, feature blocks, 2N, lanes]
    assert H.from_result_dims([8, 16, 1, 4, 1024]) == (128, 2)
    # one task: [parties, feature blocks, 2N, lanes]
    assert H.from_result_dims([16, 1, 2, 1024]) == (16, 1)
