"""Tree routing without per-row gathers (``weak_tree.trees.route``).

``HistogramTrees`` routes rows by compare-and-select over the static
node and feature ids.  These tests hold it to a plain numpy level-order
descent over the clipped bins, and to the gather descent it replaced
(kept here as the reference): predictions, every partial level the
grower routes, and the trees ``erm`` / ``erm_players`` grow are the
same bit for bit.  The compiled engines' ``predict`` step holds no
gather, so the per-row gathers cannot come back unseen.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, sharded_batched, weak
from repro.core.types import BoostConfig
from repro.kernels.histogram import ops as H
from repro.obs import trace as T
from repro.weak_tree import HistogramTrees
from repro.weak_tree import trees

COMM_MODES = ("coreset", "histogram", "voting")


def gather_descend(b, node, feat, qbin):
    """The reference: one level of descent by per-row gathers."""
    f = feat[node]
    xv = jnp.take_along_axis(b, f[..., None], axis=-1)[..., 0]
    return node * 2 + (xv >= qbin[node]).astype(jnp.int32)


def gather_route(b, feat, qbin):
    node = jnp.zeros(b.shape[:-1], jnp.int32)
    for level in range((feat.shape[0] + 1).bit_length() - 1):
        lo, hi = (1 << level) - 1, (2 << level) - 1
        node = gather_descend(b, node, feat[lo:hi], qbin[lo:hi])
    return node


def numpy_predict(cls, params, x):
    """Level-order descent over the clipped bins, one row at a time."""
    ni = cls.nodes
    feat = params[1:1 + ni].astype(np.int64)
    qbin = params[1 + ni:1 + 2 * ni].astype(np.int64)
    sign = params[1 + 2 * ni:]
    b = np.clip(np.floor(x * cls.bins), 0, cls.bins - 1).astype(np.int64)
    out = np.empty(x.shape[:-1], np.int8)
    for r in np.ndindex(*x.shape[:-1]):
        j = 0
        for _ in range(cls.depth):
            j = 2 * j + 1 + int(b[r][feat[j]] >= qbin[j])
        out[r] = 1 if sign[j - ni] > 0 else -1
    return out


def _random_tree(cls, rng):
    """A tree with some degenerate ``qbin = 0`` nodes (all rows right)."""
    feat = rng.integers(0, cls.num_features, cls.nodes)
    qbin = rng.integers(0, cls.bins, cls.nodes)
    qbin[rng.random(cls.nodes) < 0.3] = 0
    qbin[rng.integers(cls.nodes)] = 0
    sign = rng.choice([-1.0, 1.0], cls.leaves)
    return cls.pack_params(feat, qbin, sign)


def _rows(cls, rng, m):
    """Rows inside and outside [0, 1), with exact bin edges among them."""
    x = rng.uniform(-0.25, 1.25, (m, cls.num_features))
    edges = rng.integers(-1, cls.bins + 2, (m // 4, cls.num_features))
    x[: m // 4] = edges / cls.bins
    x[0] = -1e-7               # x < 0 bins to 0: qbin = 0 still goes right
    return x.astype(np.float32)


@pytest.mark.parametrize("bins", [2, 32, 256])
@pytest.mark.parametrize("F", [1, 5, 28])
@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_predict_matches_level_order_descent(depth, F, bins):
    cls = HistogramTrees(num_features=F, depth=depth, bins=bins)
    rng = np.random.default_rng(depth * 1000 + F * 10 + bins)
    x = _rows(cls, rng, 96)
    for _ in range(3):
        p = _random_tree(cls, rng)
        got = np.asarray(cls.predict(jnp.asarray(p), jnp.asarray(x)))
        assert got.dtype == np.int8
        np.testing.assert_array_equal(got, numpy_predict(cls, p, x))
        feat, qbin, _ = cls._unpack(jnp.asarray(p))
        b = H.bin_index(jnp.asarray(x), bins)
        np.testing.assert_array_equal(trees.route(b, feat, qbin),
                                      gather_route(b, feat, qbin))


@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_route_matches_gather_descent_on_partial_levels(depth):
    """Each level the grower routes: the first L levels of a tree."""
    cls = HistogramTrees(num_features=5, depth=depth, bins=32)
    rng = np.random.default_rng(depth)
    b = H.bin_index(jnp.asarray(_rows(cls, rng, 128)), cls.bins)
    feat, qbin, _ = cls._unpack(jnp.asarray(_random_tree(cls, rng)))
    for L in range(depth + 1):
        n = (1 << L) - 1
        got = trees.route(b, feat[:n], qbin[:n])
        np.testing.assert_array_equal(got, gather_route(b, feat[:n],
                                                        qbin[:n]))
        assert int(jnp.max(got)) < (1 << L)


def test_predict_over_a_parameter_batch():
    """params [T, P] (the ``ensemble_predict`` path) → [T, M]."""
    cls = HistogramTrees(num_features=5, depth=3, bins=32)
    rng = np.random.default_rng(7)
    x = _rows(cls, rng, 64)
    params = np.stack([_random_tree(cls, rng) for _ in range(6)])
    got = np.asarray(cls.predict(jnp.asarray(params.reshape(2, 3, -1)),
                                 jnp.asarray(x)))
    want = np.stack([numpy_predict(cls, p, x) for p in params])
    np.testing.assert_array_equal(got.reshape(6, -1), want)
    rounds = 5
    votes = want[:rounds].astype(np.int32).sum(axis=0)
    np.testing.assert_array_equal(
        weak.ensemble_predict(cls, jnp.asarray(params), jnp.int32(rounds),
                              jnp.asarray(x)),
        np.where(votes >= 0, 1, -1))


def test_predict_under_vmap_over_tasks():
    """One tree per task over the task's own [k, m, F] rows, jitted."""
    cls = HistogramTrees(num_features=28, depth=2, bins=32)
    rng = np.random.default_rng(11)
    params = np.stack([_random_tree(cls, rng) for _ in range(3)])
    x = np.stack([_rows(cls, rng, 64).reshape(4, 16, 28)
                  for _ in range(3)])
    got = jax.jit(jax.vmap(cls.predict))(jnp.asarray(params),
                                         jnp.asarray(x))
    for t in range(3):
        np.testing.assert_array_equal(got[t],
                                      numpy_predict(cls, params[t], x[t]))


def _coreset(cls, rng, kp=4, c=48):
    x = cls.sample_points(rng, kp * c).reshape(kp, c, cls.num_features)
    x[0, :3, 0] = [-0.1, 1.0, 1.2]            # rows the bin map clips
    y = np.where(rng.random((kp, c)) < 0.5, 1, -1).astype(np.int8)
    pw = np.full(kp, 1.0 / (kp * c), np.float32)
    pw[-1] = 0.0                              # a dead player
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(pw)


def _grown(cls, mode, cx, cy, pw):
    if mode == "erm":
        kp, c = cy.shape
        w = jnp.repeat(pw, c)
        return cls.erm(cx.reshape(kp * c, -1), cy.reshape(-1), w)
    return cls.erm_players(cx, cy, pw)


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("mode", ("erm",) + COMM_MODES)
def test_growers_grow_the_trees_the_gather_descent_grew(depth, mode,
                                                        monkeypatch):
    cls = HistogramTrees(num_features=5, depth=depth, bins=16,
                         comm_mode="histogram" if mode == "erm" else mode)
    cx, cy, pw = _coreset(cls, np.random.default_rng(depth))
    params, loss = _grown(cls, mode, cx, cy, pw)
    monkeypatch.setattr(trees, "descend", gather_descend)
    ref_params, ref_loss = _grown(cls, mode, cx, cy, pw)
    np.testing.assert_array_equal(params, ref_params)
    assert float(loss) == float(ref_loss)


def _lower(engine):
    """The toy tree engine, compiled (as tests/test_round_steps.py)."""
    B, K, MLOC, F = 2, 4, 64, 4
    cls = weak.make_class("tree", num_features=F, tree_depth=2,
                          tree_bins=8, tree_comm_mode="histogram")
    cfg = BoostConfig(k=K, coreset_size=16, domain_size=1 << 20,
                      opt_budget=4, deterministic_coreset=False)
    x = (np.random.default_rng(0).integers(0, 8, size=(B, K, MLOC, F))
         / 8).astype(np.float32)
    y = np.where(x[..., 0] > 0.5, 1, -1).astype(np.int8)
    alive = np.ones(y.shape, bool)
    keys = jax.random.split(jax.random.key(1), B)
    if engine == "sharded":
        return sharded_batched.lower_classify_sharded(
            x, y, alive, keys, cfg, cls,
            sharded_batched.make_players_mesh(K))
    return batched.lower_classify(x, y, alive, keys, cfg, cls)


@pytest.mark.parametrize("engine", ["batched", "sharded"])
def test_predict_step_compiles_to_no_gather(engine):
    comps = T.parse_hlo(_lower(engine).as_text())
    ops = [(op, step) for instrs in comps.values()
           for _, op, step, *_ in instrs]
    assert any(step == "predict" for _, step in ops)
    assert [op for op, step in ops
            if op == "gather" and step == "predict"] == []
