"""The plain reference on tasks small enough to check by hand."""

import math

import numpy as np
import pytest

import reference as R

THR = {"class": "thresholds", "domain": 16, "k": 2, "coreset": 4}
TREE = {"class": "tree", "features": 2, "depth": 2, "bins": 8,
        "comm_mode": "histogram", "k": 2, "coreset": 4}


def test_opt_is_the_fewest_errors_of_any_classifier():
    x = np.array([1, 1, 1, 2, 3, 3])
    y = np.array([1, 1, -1, 1, -1, 1])
    assert R.opt_all(x, y) == 1 + 0 + 1


def test_threshold_ensemble_votes_with_sign_zero_as_plus():
    hyps = np.array([[2, 3, 3, 1], [2, 5, 5, -1], [2, 0, 0, 1]], float)
    x = np.array([0, 3, 4, 5, 9])
    # h0: x ≥ 3 → +1; h1: x ≥ 5 → −1 else +1
    assert R.votes(THR, hyps, 2, x).tolist() == [0, 2, 2, 0, 0]
    f = R.classify(THR, hyps, 2, x, np.ones(5, int), np.zeros(5, bool))
    assert f.tolist() == [1, 1, 1, 1, 1]


def test_disputed_points_take_the_majority_of_all_their_copies():
    hyps = np.array([[2, 3, 3, 1]], float)
    x = np.array([1, 1, 1, 5, 5])
    y = np.array([-1, -1, 1, 1, -1])
    disputed = np.array([True, False, False, True, False])
    f = R.classify(THR, hyps, 1, x, y, disputed)
    # point 1: two −, one + → −1 on every copy; point 5: tie → +1
    assert f.tolist() == [-1, -1, -1, 1, 1]


def test_tree_routes_level_by_level_on_bins():
    nodes = 3
    p = np.zeros(1 + 2 * nodes + 4)
    p[1:1 + nodes] = [0, 1, 1]            # root on f0, children on f1
    p[1 + nodes:1 + 2 * nodes] = [4, 2, 6]
    p[1 + 2 * nodes:] = [1, -1, -1, 1]
    x = (np.array([[1, 1], [1, 5], [6, 5], [6, 7]]) + 0.5) / 8
    # leaves: (f0<4, f1<2)=0, (f0<4, f1≥2)=1, (f0≥4, f1<6)=2, (≥4, ≥6)=3
    assert R.votes(TREE, p[None], 1, x).tolist() == [1, -1, -1, 1]


def test_round_count_is_the_papers():
    assert R.num_rounds(1 << 16) == 96
    assert R.num_rounds(65535) == 96
    assert R.num_rounds(1 << 20) == 120


@pytest.mark.parametrize("m", [2, 3, 6, 8, 255, 4097, 56950, 65536,
                               1048575, 1048576, 10500000])
def test_bit_counts_in_integers_match_the_papers_formulas(m):
    T = math.ceil(6 * math.log2(m))
    assert R.num_rounds(m) == T
    assert R.weight_sum_bits(m, T) == (
        math.ceil(math.log2(T + math.log2(m))) + math.ceil(math.log2(m)))


def test_threshold_hypothesis_bits_at_the_deployment_domain():
    # a threshold at one of 2^20 + 1 places: 21 bits, plus kind and sign;
    # float32 log2(2^20 + 1) rounds to 20 and would give 23
    assert R.class_bits(dict(THR, domain=1 << 20)) == (20, 24, 0)


def test_ledger_counts_the_message_pattern():
    # attempt 1: 3 rounds then stuck (4 wire rounds) on 8 rows, 2 points
    # of its coreset quarantined; attempt 2: 9 rounds on the 6 left
    lane = {"attempts": 2, "hist_rounds": [3, 9], "hist_stuck": [True, False],
            "hist_alive": [8, 6], "hist_p": [2, 0]}
    # point 4 bits, example 5, hypothesis 8; weight sums 8 bits (T = 18
    # at m = 8, 16 at m = 6)
    first = 4 * 2 * 4 * 5 + 4 * 2 * 8 + 3 * 2 * 8 + 2 + 2
    second = 9 * 2 * 4 * 5 + 9 * 2 * 8 + 9 * 2 * 8 + 2
    dispute = 2 * 2 * (4 + 2 * 3)
    assert R.ledger_bits(THR, lane, 8) == first + second + dispute
    # histogram mode: examples cross on the stuck round alone, and each
    # wire round ships 2·nodes·F·Q histogram cells
    tree = dict(TREE)
    point, hyp, hist = R.class_bits(tree)
    assert (point, hyp, hist) == (6, 3 * (1 + 3) + 4, 2 * 3 * 2 * 8)
    lane = {"attempts": 1, "hist_rounds": [18], "hist_stuck": [False],
            "hist_alive": [8], "hist_p": [0]}
    assert R.ledger_bits(tree, lane, 8) == (
        18 * 2 * hist * 8 + 18 * 2 * 8 + 18 * 2 * hyp + 2)


def test_task_numbers_of_a_perfect_lane():
    x = np.array([[0, 1, 2, 3], [8, 9, 10, 11]])
    y = np.where(x >= 8, 1, -1)
    m = 8
    T = R.num_rounds(m)
    lane = {"hypotheses": np.tile([2.0, 8.0, 8.0, 1.0], (T, 1)),
            "rounds": T, "disputed": np.zeros((2, 4), bool),
            "hist_rounds": [T], "hist_stuck": [False], "attempts": 1,
            "hist_alive": [m], "hist_p": [0]}
    lane["ledger_bits"] = R.ledger_bits(THR, lane, m)
    got = R.task_numbers(THR, x, y, np.ones((2, 4), bool), lane)
    assert got == {"excess_errors": 0, "round_gap": 0, "ledger_gap": 0}
    lane["ledger_bits"] += 2 * 8          # one more broadcast charged
    assert R.task_numbers(THR, x, y, np.ones((2, 4), bool),
                          lane)["ledger_gap"] == 16
    lane["hypotheses"] = lane["hypotheses"] * [1, 1, 1, -1]
    assert R.task_numbers(THR, x, y, np.ones((2, 4), bool),
                          lane)["excess_errors"] == 8
    lane["rounds"] = T // 2
    assert R.task_numbers(THR, x, y, np.ones((2, 4), bool),
                          lane)["round_gap"] == T - T // 2
