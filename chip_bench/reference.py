"""Plain reference for what a dispatch returns, in numpy alone.

It imports nothing of the program.  For one task it takes the sample
the benchmark generated (x, y, the rows that are real) and what the
timed path returned for that task's lane — the ensemble (hypothesis
parameters and the winning attempt's round count), the quarantine mask,
the per-attempt histories and the Theorem 4.1 ledger the service
reports — and reads the numbers the configuration promises (Theorem 4.1
of Filmus, Mehalel & Moran, ICML 2022):

* ``excess_errors`` = E_S(f) − OPT_S, where f is the dispute vote
  patched over the boosted ensemble, evaluated here from the returned
  parameters, and OPT_S = Σ over distinct points of min(n₊, n₋) is the
  fewest errors any classifier can make on S.  Promise: ≤ 0.
* ``round_gap`` = |rounds of the winning attempt − ⌈6·log2 m_alive⌉|,
  m_alive the rows left once the quarantined ones are gone.  Promise: 0.
* ``ledger_gap`` = |bits the service's ledger charges − bits of the
  paper's message pattern over the attempts the histories record|,
  counted here in integers.  Promise: 0.
"""

from __future__ import annotations

import numpy as np

ROUNDS_FACTOR = 6


def bin_ids(x: np.ndarray, Q: int) -> np.ndarray:
    return np.clip(np.floor(x * np.float32(Q)).astype(np.int64), 0, Q - 1)


def votes(config: dict, hyps: np.ndarray, rounds: int,
          x: np.ndarray) -> np.ndarray:
    """Σ_{t < rounds} h_t(x) over the rows of x ([M] or [M, F])."""
    total = np.zeros(x.shape[0], np.int64)
    if config["class"] == "thresholds":
        for a, s in hyps[:rounds, [1, 3]]:
            total += np.where(x >= a, 1, -1) * (1 if s > 0 else -1)
        return total
    D = config["depth"]
    nodes, leaves = (1 << D) - 1, 1 << D
    cols = np.ascontiguousarray(bin_ids(x, config["bins"]).T
                                .astype(np.int16))       # [F, M]
    node = np.empty(x.shape[0], np.int8)
    right = np.empty(x.shape[0], bool)
    for p in hyps[:rounds]:
        feat = p[1:1 + nodes].astype(np.int64)
        qbin = p[1 + nodes:1 + 2 * nodes].astype(np.int16)
        sign = p[1 + 2 * nodes:1 + 2 * nodes + leaves]
        node[:] = 0
        for level in range(D):
            for j in range(1 << level):              # the level's nodes
                at = (1 << level) - 1 + j
                np.copyto(right, cols[feat[at]] >= qbin[at],
                          where=node == j)
            node *= 2
            node += right
        positive = np.isin(node, np.flatnonzero(sign > 0))
        total += 2 * positive.astype(np.int64) - 1
    return total


def groups(x: np.ndarray) -> np.ndarray:
    """Index of each row's distinct point."""
    if x.ndim == 1:
        return np.unique(x, return_inverse=True)[1].reshape(-1)
    rows = np.ascontiguousarray(x).view(
        np.dtype((np.void, x.dtype.itemsize * x.shape[1])))
    return np.unique(rows.reshape(-1), return_inverse=True)[1].reshape(-1)


def label_counts(g: np.ndarray, y: np.ndarray):
    """(n₊, n₋) of each distinct point."""
    size = int(g.max()) + 1
    return (np.bincount(g[y > 0], minlength=size),
            np.bincount(g[y < 0], minlength=size))


def classify(config: dict, hyps, rounds: int, x, y, disputed, g=None):
    """f on the rows of S: the dispute vote (n₊ ≥ n₋ → +1) where a row's
    point was quarantined, else the ensemble's sign (0 → +1)."""
    f = np.where(votes(config, hyps, rounds, x) >= 0, 1, -1)
    g = groups(x) if g is None else g
    pos, neg = label_counts(g, y)
    in_d = np.zeros(pos.shape[0], bool)
    in_d[g[disputed]] = True
    vote = np.where(pos >= neg, 1, -1)
    return np.where(in_d[g], vote[g], f)


def opt_all(x: np.ndarray, y: np.ndarray, g=None) -> int:
    pos, neg = label_counts(groups(x) if g is None else g, y)
    return int(np.minimum(pos, neg).sum())


def ceil_log2(v: int) -> int:
    """⌈log2 v⌉ of a whole number v ≥ 1, in integers."""
    return (int(v) - 1).bit_length()


def num_rounds(m: int) -> int:
    """T = ⌈6·log2 m⌉: the least T with 2^T ≥ m^6."""
    return ceil_log2(max(m, 2) ** ROUNDS_FACTOR)


def weight_sum_bits(m: int, T: int) -> int:
    """A weight sum on the wire: log2 W in [−T, log2 m], fixed point with
    ⌈log2 m⌉ fraction bits, so ⌈log2(T + log2 m)⌉ + ⌈log2 m⌉ bits.  As T
    is whole, T + log2 m ≤ 2^b exactly when T + ⌈log2 m⌉ ≤ 2^b."""
    lm = ceil_log2(max(m, 2))
    return max(ceil_log2(T + lm), 1) + lm


def class_bits(config: dict):
    """(point bits, hypothesis bits, histogram scalars per party)."""
    if config["class"] == "thresholds":
        n = config["domain"]
        # a threshold at one of n + 1 places, its kind and its sign
        return max(1, ceil_log2(n)), ceil_log2(n + 1) + 3, 0
    F, Q, D = config["features"], config["bins"], config["depth"]
    nodes = (1 << D) - 1
    bin_bits = ceil_log2(Q)
    point = F * bin_bits
    hyp = nodes * (max(1, ceil_log2(F)) + bin_bits) + (1 << D)
    hist = 2 * nodes * F * Q if config["comm_mode"] == "histogram" else 0
    return point, hyp, hist


def ledger_bits(config: dict, lane: dict, m_true: int) -> int:
    """Bits of the paper's message pattern over a task's attempts: per
    wire round k coresets (in histogram mode k histogram sets, and the
    coresets on the stuck round alone) and k weight sums, k hypothesis
    broadcasts per produced hypothesis, k halt bits per attempt and k
    stuck flags; per point of a stuck round's coreset (``hist_p``) one
    broadcast to each party and two counts back from each."""
    k, c = config["k"], config["coreset"]
    point, hyp, hist = class_bits(config)
    example = point + 1
    total = 0
    disputed_points = 0
    for a in range(int(lane["attempts"])):
        rounds = int(lane["hist_rounds"][a])
        stuck = int(bool(lane["hist_stuck"][a]))
        m = max(int(lane["hist_alive"][a]), 2)
        wire = rounds + stuck
        ws = weight_sum_bits(m, num_rounds(m))
        if hist:
            total += stuck * k * c * example + wire * k * hist * ws
        else:
            total += wire * k * c * example
        total += wire * k * ws + rounds * k * hyp + k * stuck + k
        disputed_points += int(lane["hist_p"][a]) * stuck
    total += k * disputed_points * (point + 2 * max(ceil_log2(m_true), 1))
    return total


def task_numbers(config: dict, x, y, real, lane: dict) -> dict:
    """The compared numbers of one task.  ``x``/``y``/``real`` are the
    task's [k, mloc(, F)] / [k, mloc] sample and real-row mask; ``lane``
    holds what the timed path returned for it (see module docstring)."""
    flat = real.reshape(-1)
    xs = x.reshape((-1,) + x.shape[2:])[flat]
    ys = y.reshape(-1)[flat]
    dis = np.asarray(lane["disputed"]).reshape(-1)[flat]
    g = groups(xs)
    f = classify(config, np.asarray(lane["hypotheses"]),
                 int(lane["rounds"]), xs, ys, dis, g)
    m_true = int(flat.sum())
    return {
        "excess_errors": int((f != ys).sum()) - opt_all(xs, ys, g),
        "round_gap": abs(int(lane["rounds"])
                         - num_rounds(m_true - int(dis.sum()))),
        "ledger_gap": abs(int(lane["ledger_bits"])
                          - ledger_bits(config, lane, m_true)),
    }
