"""System behaviour of the paper's protocol (Theorems 2.2 / 4.1).

* realizable samples: BoostAttempt never gets stuck and outputs a
  consistent classifier (Lemma 4.2);
* noisy samples: AccuratelyClassify achieves E_S(f) ≤ OPT within
  ≤ OPT + 1 attempts (Observation 4.4);
* no contradicting examples ⇒ E_S(f) = 0 (Theorem 4.1);
* measured communication respects the Theorem 4.1 bound shape;
* the deterministic quantile coreset is a true 1/100-approximation;
* the shard_map production form computes the same protocol.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import (approximation, boost_attempt, classify, ledger,
                        tasks, weak, weights)
from repro.core.types import BoostConfig

N = 1 << 12


def _learn(cls, task, cfg, seed=0):
    f, res = classify.learn(jnp.asarray(task.x), jnp.asarray(task.y),
                            jax.random.key(seed), cfg, cls)
    preds = f(jnp.asarray(task.flat_x))
    errs = int(weak.empirical_errors(preds, jnp.asarray(task.flat_y)))
    return f, res, errs


@pytest.mark.parametrize("clsname", ["thresholds", "intervals",
                                     "singletons"])
def test_realizable_consistent(clsname):
    cls = weak.make_class(clsname, n=N)
    cfg = BoostConfig(k=4, coreset_size=400, domain_size=N, opt_budget=4)
    task = tasks.make_task(cls, m=2048, k=4, noise=0, seed=7)
    f, res, errs = _learn(cls, task, cfg)
    assert res.attempts == 1 and not res.stuck_history[0]
    assert errs == 0


@pytest.mark.parametrize("clsname,noise,seed", [
    ("thresholds", 4, 0), ("thresholds", 8, 1), ("intervals", 4, 2),
    ("intervals", 8, 3), ("singletons", 4, 4), ("singletons", 8, 5),
])
def test_noisy_at_most_opt(clsname, noise, seed):
    cls = weak.make_class(clsname, n=N)
    cfg = BoostConfig(k=4, coreset_size=400, domain_size=N,
                      opt_budget=32)
    task = tasks.make_task(cls, m=2048, k=4, noise=noise, seed=seed)
    opt = tasks.true_opt(task)
    f, res, errs = _learn(cls, task, cfg, seed)
    assert errs <= opt, (errs, opt)
    assert res.attempts <= opt + 1           # Observation 4.4


def test_no_contradictions_zero_error():
    """noise flips distinct points; as long as the flipped point has a
    single occurrence there are no contradicting examples at the same
    point with both labels UNLESS duplicates — construct explicitly."""
    cls = weak.Thresholds(n=N)
    rng = np.random.default_rng(0)
    x = rng.choice(N, size=1024, replace=False).astype(np.int32)  # unique
    y = np.where(x >= 2000, 1, -1).astype(np.int8)
    y[:5] = -y[:5]                            # noise, but no contradictions
    cfg = BoostConfig(k=4, coreset_size=400, domain_size=N, opt_budget=32)
    xk = jnp.asarray(x.reshape(4, -1))
    yk = jnp.asarray(y.reshape(4, -1))
    f, res = classify.learn(xk, yk, jax.random.key(0), cfg, cls)
    errs = int(weak.empirical_errors(f(jnp.asarray(x)), jnp.asarray(y)))
    assert errs == 0                          # Theorem 4.1, furthermore-part


def test_communication_bound_shape():
    """Measured bits ≤ constant × OPT·k·log|S|·(coreset·log n + log|S|)."""
    cls = weak.Thresholds(n=N)
    cfg = BoostConfig(k=4, coreset_size=400, domain_size=N, opt_budget=64)
    for noise, seed in ((0, 0), (5, 1), (10, 2)):
        task = tasks.make_task(cls, m=4096, k=4, noise=noise, seed=seed)
        opt = tasks.true_opt(task)
        _, res, errs = _learn(cls, task, cfg, seed)
        bound = ledger.theorem_41_bound(cfg, cls, 4096, opt, constant=4.0)
        assert res.ledger.total_bits <= bound, (noise, res.ledger.total_bits,
                                                bound)
        # protocol must beat sending the raw data once OPT is small
        naive = ledger.naive_baseline_bits(4096, N)
        assert res.ledger.total_bits < 60 * naive  # sanity ceiling


def test_quantile_coreset_is_approximation():
    """|L_{S'}(h) − L_p(h)| ≤ 1/100 for all thresholds (c = 400)."""
    rng = np.random.default_rng(3)
    m = 2048
    x = jnp.asarray(rng.integers(0, N, m), jnp.int32)
    y = jnp.asarray(rng.choice([-1, 1], m), jnp.int8)
    hits = jnp.asarray(rng.integers(0, 12, m), jnp.int32)
    alive = jnp.asarray(rng.random(m) < 0.9)
    idx = approximation.quantile_coreset(x, y, hits, alive, c=400)
    cls = weak.Thresholds(n=N)
    grid = jnp.asarray(
        [[2.0, t, t, s] for t in range(0, N, 7) for s in (1.0, -1.0)],
        jnp.float32)
    err = approximation.approximation_error(
        idx, x, y, hits, alive, cls.predict, grid)
    assert float(err) <= 1.0 / 100.0 + 1e-6, float(err)


def test_sharded_equals_reference():
    """shard_map form on a 1-device mesh reproduces the k=1 reference."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cls = weak.Thresholds(n=N)
    task = tasks.make_task(cls, m=1024, k=1, noise=0, seed=5)
    cfg = BoostConfig(k=1, coreset_size=400, domain_size=N)
    T = cfg.num_rounds(1024)
    fn = boost_attempt.boost_attempt_sharded(mesh, cfg, cls, num_rounds=T)
    x = jnp.asarray(task.x.reshape(-1))
    y = jnp.asarray(task.y.reshape(-1))
    t, stuck, hits, h_params, loss = fn(
        x, y, jnp.ones_like(x, bool), jnp.zeros_like(x), jax.random.key(0))
    assert not bool(stuck)
    g = weak.ensemble_predict(cls, h_params, int(t), x)
    assert int(weak.empirical_errors(g, y)) == 0
    # reference single-process run also consistent
    res = boost_attempt.run_boost_attempt(
        jnp.asarray(task.x), jnp.asarray(task.y),
        jnp.ones_like(jnp.asarray(task.x), bool), jax.random.key(0),
        cfg, cls)
    assert not res.stuck


def test_log_weight_math():
    rng = np.random.default_rng(1)
    hits = jnp.asarray(rng.integers(0, 40, 256), jnp.int32)
    alive = jnp.asarray(rng.random(256) < 0.8)
    # float64 oracle on host numpy: jnp.float64 would silently truncate
    # to f32 with x64 off (and now warns-as-errors under pytest.ini)
    direct = float(np.sum(np.where(np.asarray(alive),
                                   2.0 ** (-np.asarray(hits, np.float64)),
                                   0.0)))
    lw = float(weights.log_weight_sum(hits, alive))
    np.testing.assert_allclose(2.0 ** lw, direct, rtol=1e-5)
    p = weights.probs(hits, alive)
    np.testing.assert_allclose(float(jnp.sum(p)), 1.0, rtol=1e-5)
    assert float(jnp.max(jnp.where(alive, 0.0, p))) == 0.0


def test_no_center_model_equivalent():
    """§2.2: the no-center protocol (player 0 acts as center) produces
    a consistent classifier identical in outcome to the center model."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cls = weak.Thresholds(n=N)
    task = tasks.make_task(cls, m=1024, k=1, noise=0, seed=9)
    cfg = BoostConfig(k=1, coreset_size=400, domain_size=N)
    T = cfg.num_rounds(1024)
    x = jnp.asarray(task.x.reshape(-1))
    y = jnp.asarray(task.y.reshape(-1))
    args = (x, y, jnp.ones_like(x, bool), jnp.zeros_like(x),
            jax.random.key(0))
    fn_c = boost_attempt.boost_attempt_sharded(mesh, cfg, cls, T)
    fn_n = boost_attempt.boost_attempt_sharded(mesh, cfg, cls, T,
                                               no_center=True)
    tc, sc, _, hc, _ = fn_c(*args)
    tn, sn, _, hn, _ = fn_n(*args)
    assert int(tc) == int(tn) and bool(sc) == bool(sn)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hn),
                               rtol=1e-6)
    g = weak.ensemble_predict(cls, hn, int(tn), x)
    assert int(weak.empirical_errors(g, y)) == 0
