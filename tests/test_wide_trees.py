"""Tree tenants at Epsilon's width on the CPU: 2,000 features on 256
bins, the widths of the benchmark's ``trees-f2k-q256`` deployment,
with parties, coresets and rows cut so that each test takes seconds.

* the host loop and the batched engine give the same hypotheses,
  rounds, quarantine and ledger, bit for bit;
* the benchmark's plain reference reads the engine's round count and
  ledger exactly;
* the Pallas kernel, interpreted, at 125 feature blocks equals the
  jnp histograms bit for bit on dyadic weights.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, classify, weak
from repro.core.types import BoostConfig
from repro.kernels.histogram import kernel as K
from repro.kernels.histogram.ref import node_histograms_ref

F, Q = 2000, 256
# toy parties, coresets and rows: the quarantine's all-pairs point
# count is P²·F compares, P = k·c = 128.  A coreset this small rarely
# holds the planted concept's splits against 512,000 candidates, so
# most attempts stick at once and the quarantine runs many times
K_PARTIES, C, M = 4, 32, 512
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_bench")


def _bench_module(name):
    spec = importlib.util.spec_from_file_location(
        "wide_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def wide():
    """One task of the benchmark's generator at the cell's widths, and
    what the batched engine returns for it."""
    taskgen = _bench_module("taskgen")
    cls = weak.make_class("tree", num_features=F, tree_depth=2,
                          tree_bins=Q, tree_comm_mode="histogram")
    cfg = BoostConfig(k=K_PARTIES, coreset_size=C, domain_size=1 << 20,
                      opt_budget=32, deterministic_coreset=False)
    x, y = taskgen.xor_tree_task(taskgen.seed_rng(2 ** 33 + 5, 0), M,
                                 K_PARTIES, F, Q, 2)
    key = jax.random.key(7)
    res = batched.run_accurately_classify_batched(
        x[None], y[None], key[None], cfg, cls)
    return cls, cfg, x, y, key, res


def test_host_loop_and_batched_engine_agree_bit_for_bit(wide):
    cls, cfg, x, y, key, res = wide
    href = classify.run_accurately_classify(jnp.asarray(x), jnp.asarray(y),
                                            key, cfg, cls)
    got = res.per_task(0)
    assert got.attempts >= 2                   # a quarantine ran
    assert (href.attempts, href.rounds) == (got.attempts, got.rounds)
    assert href.stuck_history == got.stuck_history
    np.testing.assert_array_equal(
        np.asarray(href.hypotheses)[:href.rounds],
        np.asarray(got.hypotheses)[:got.rounds])

    def rowsort(a):
        a = np.asarray(a)
        return a[np.lexsort(a.T[::-1])]

    np.testing.assert_array_equal(rowsort(href.dispute_x),
                                  rowsort(got.dispute_x))
    xs = jnp.asarray(x.reshape(-1, F))
    np.testing.assert_array_equal(
        np.asarray(classify.make_classifier(cls, href)(xs)),
        np.asarray(classify.make_classifier(cls, got)(xs)))
    assert href.ledger == got.ledger


def test_reference_reads_the_engine_round_count_and_ledger(wide):
    cls, cfg, x, y, key, res = wide
    reference = _bench_module("reference")
    config = {"class": "tree", "features": F, "bins": Q, "depth": 2,
              "comm_mode": "histogram", "k": K_PARTIES, "coreset": C}
    n = int(res.attempts[0])
    lane = {"hypotheses": res.hypotheses[0], "rounds": int(res.rounds[0]),
            "disputed": res.disputed[0], "attempts": n,
            "hist_rounds": res.hist_rounds[0],
            "hist_stuck": res.hist_stuck[0],
            "hist_alive": res.hist_alive[0], "hist_p": res.hist_p[0],
            "ledger_bits": int(res.ledger(0).total_bits)}
    got = reference.task_numbers(config, x, y, np.ones(y.shape, bool),
                                 lane)
    assert got["round_gap"] == 0 and got["ledger_gap"] == 0
    # a party's histograms are 2·nodes·F·Q scalars: 3,072,000 here
    assert reference.class_bits(config)[2] == 3 * 2 * F * Q


def test_feature_block_at_epsilon_width():
    assert K.feature_block(F, Q) == (16, 2000)
    assert K.vmem_bytes(2, F, Q) < 16 * 2 ** 20


def test_interpreted_kernel_equals_the_jnp_histograms_at_2000_features():
    """One unit, c = 256 points, N = 2 nodes: 125 feature blocks of
    16 × 256 bins.  Weights on a dyadic grid, so every cell is an exact
    float32 sum and the two must agree bit for bit."""
    rng = np.random.default_rng(3)
    c, N = 256, 2
    x = ((rng.integers(0, Q, (c, F)) + 0.5) / Q).astype(np.float32)
    w = (rng.integers(0, 64, (N, c)) / 1024.0).astype(np.float32)
    wy = w * rng.choice(np.array([-1.0, 1.0], np.float32), (N, c))
    hw, hwy = K.hist_pallas(jnp.asarray(x), jnp.asarray(w),
                            jnp.asarray(wy), bins=Q, interpret=True)
    rw, rwy = node_histograms_ref(jnp.asarray(x), jnp.asarray(w),
                                  jnp.asarray(wy), Q)
    np.testing.assert_array_equal(np.asarray(hw), np.asarray(rw))
    np.testing.assert_array_equal(np.asarray(hwy), np.asarray(rwy))
