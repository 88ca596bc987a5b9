"""The benchmark's own task generator: labelled samples from a seed.

Numpy only, so the workload cannot move when the program's generators
change.  Two kinds of task, each split over k parties adversarially
(sorted by the first coordinate, so each party holds one region):

* ``thresholds`` — m integer points of [0, n), labelled by a threshold
  at a random quantile in [0.2, 0.8] with a random sign.  Noise
  ``targeted_heavy`` flips one copy of each of the ``noise`` most
  duplicated points (ties by value), so every flip is a contradiction
  that no classifier can avoid; ``clean`` flips nothing.
* ``tree`` — m grid-snapped points of [0, 1)^F on Q bins, labelled by a
  planted XOR of two off-centre half-lines of two features (a depth-2
  tree: the paper's setting, a concept of the class), then ``noise``
  uniform flips on distinct rows.

A task is ``(x [k, m/k(, F)], y [k, m/k] int8 ±1)``.
"""

from __future__ import annotations

import numpy as np


def seed_rng(*words: int) -> np.random.Generator:
    """A generator keyed by any non-negative integers (seeds past 2^32
    included)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) for w in words]))


def _split(x: np.ndarray, y: np.ndarray, k: int):
    m = y.shape[0]
    if m % k:
        raise ValueError(f"k={k} must divide m={m}")
    key = x if x.ndim == 1 else x[:, 0]
    order = np.argsort(key, kind="stable")
    return (x[order].reshape((k, m // k) + x.shape[1:]),
            y[order].reshape(k, m // k))


def _flip_heaviest(x: np.ndarray, noise: int) -> np.ndarray:
    vals, first, counts = np.unique(x, return_index=True,
                                    return_counts=True)
    if noise and counts.max(initial=0) < 2:
        raise ValueError("targeted_heavy needs duplicated points")
    order = np.lexsort((vals, -counts))
    flip = np.zeros(x.shape[0], bool)
    flip[first[order[:noise]]] = True
    return flip


def threshold_task(rng: np.random.Generator, m: int, k: int, n: int,
                   scenario: str, noise: int):
    x = rng.integers(0, n, size=m).astype(np.int32)
    theta = np.floor(np.quantile(x, rng.uniform(0.2, 0.8)))
    s = rng.choice(np.array([-1, 1], np.int8))
    y = np.where(x >= theta, s, -s).astype(np.int8)
    if scenario == "targeted_heavy":
        flip = _flip_heaviest(x, noise)
    elif scenario == "clean":
        flip = np.zeros(m, bool)
    else:
        raise ValueError(f"unknown threshold scenario {scenario!r}")
    y[flip] = -y[flip]
    return _split(x, y, k)


def xor_tree_task(rng: np.random.Generator, m: int, k: int, F: int,
                  Q: int, noise: int):
    b = rng.integers(0, Q, size=(m, F))
    x = ((b + 0.5) / Q).astype(np.float32)
    f1, f2 = rng.choice(F, size=2, replace=False)
    qa = int(rng.integers(5 * Q // 16, 3 * Q // 8 + 1))
    qb = int(rng.integers(5 * Q // 8, 11 * Q // 16 + 1))
    s0 = rng.choice(np.array([-1, 1], np.int8))
    y = np.where((b[:, f1] >= qa) != (b[:, f2] >= qb), s0,
                 -s0).astype(np.int8)
    flip = np.zeros(m, bool)
    flip[rng.choice(m, size=noise, replace=False)] = True
    y[flip] = -y[flip]
    return _split(x, y, k)


def make_task(config: dict, rng: np.random.Generator, m: int,
              scenario: str, noise: int):
    """One task of a configuration's class, ``scenario`` permitting."""
    if config["class"] == "thresholds":
        return threshold_task(rng, m, config["k"], config["domain"],
                              scenario, noise)
    if config["class"] == "tree":
        if scenario != "xor":
            raise ValueError(f"tree tasks plant xor, not {scenario!r}")
        return xor_tree_task(rng, m, config["k"], config["features"],
                             config["bins"], noise)
    raise ValueError(f"unknown class {config['class']!r}")


def make_batch(config: dict, seed: int, batch_id: int, B: int, m: int,
               scenario: str, noise: int):
    """B tasks stacked for one dispatch: (x [B, k, m/k(, F)], y)."""
    tasks = [make_task(config, seed_rng(seed, batch_id, b), m, scenario,
                       noise) for b in range(B)]
    return (np.stack([t[0] for t in tasks]),
            np.stack([t[1] for t in tasks]))
