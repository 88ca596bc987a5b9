"""AccuratelyClassify (Figure 2) — the resilient learning protocol.

Outer loop: run BoostAttempt; while it returns a non-realizable coreset
S', quarantine S' (dispute multiset D — Observation 4.4 guarantees every
hypothesis' error drops by ≥ 1, so at most OPT iterations) and retry.
When an attempt succeeds, the final classifier is the dispute majority
vote patched over the boosted ensemble g.

Full-point quarantine (documented deviation, see DESIGN.md §8).  The
paper removes exactly the sub-multiset S' and votes over D-counts only.
When an ε-approximation captures only *some* copies of a point x (or
copies at one player but not another), the D-vote can disagree with the
overall majority at x and f errs up to OPT + O(1) — we observed exactly
this off-by-one empirically.  We therefore quarantine **every copy of
every disputed point, across all players**:

* the center broadcasts the stuck coreset's point set
  (|S'|·⌈log2 n⌉ bits to each of k players — same order as the coreset
  transmission itself);
* each player deletes all local copies and reports per-point label
  counts (2·⌈log2 m⌉ bits per point), which the center accumulates into
  the dispute table n₊/n₋;
* f(x) votes with the **full** counts of x in S, so
  E_S(f) = Σ_{x∈D} min(n₊, n₋) ≤ min over ALL classifiers ≤ OPT,
  unconditionally — which is precisely the guarantee Theorem 4.1 states
  ("makes the least number of errors among all possible classifiers").

Guarantees: E_S(f) ≤ OPT always; E_S(f) = 0 when S has no contradicting
examples; communication O(OPT · k·log|S|·(d log n + log|S|)) — the two
new messages add O(OPT·k·(log n + log m)) per disputed point, absorbed
by the same bound.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import boost_attempt, ledger as L, weak
from repro.core.types import BoostConfig, ClassifyResult, Ledger
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Array-form quarantine primitives (jit-safe; used by core/batched.py).
#
# The host loop below dedupes the stuck coreset with np.unique;
# on device the same semantics are masked point-matching: an example
# dies iff its point equals ANY entry of the stuck coreset, and the
# dispute-table size P is the number of distinct coreset values.
# Matching m points against P is a sort of the P and a binary search
# per point, O((m+P)·log P): the [m, P] compare it replaced held 8.6 GB
# at B = 8, m = 2^17, P = k·coreset_size = 8192 — more than half a v5e.
# ---------------------------------------------------------------------------

def _sort_order(pts: jax.Array) -> jax.Array:
    """Stable sort order of points, rows lexicographically (feature 0
    major).  JAX sorts −0.0 equal to +0.0 and every NaN last, so the
    order agrees with ``==`` and with :func:`_search`'s comparison.

    Rows take one stable single-key sort per feature, last feature
    first, in a loop: the TPU compiler did not finish a 28-key sort
    (``jnp.lexsort``) in minutes, and compiles this loop's one sort in
    seconds for any F."""
    if pts.ndim == 1:
        return jnp.argsort(pts, stable=True)
    F = pts.shape[1]

    def by_feature(i, order):
        key = jnp.take(pts[order], F - 1 - i, axis=1)
        return order[jnp.argsort(key, stable=True)]

    return jax.lax.fori_loop(0, F, by_feature,
                             jnp.arange(pts.shape[0], dtype=jnp.int32))


def _equal(a: jax.Array, b: jax.Array) -> jax.Array:
    return a == b if a.ndim == 1 else jnp.all(a == b, axis=-1)


def _search(ps: jax.Array, q: jax.Array, side: str) -> jax.Array:
    """``searchsorted`` of q [M] / [M, F] into sorted ps [P] / [P, F]."""
    if ps.ndim == 1:
        return jnp.searchsorted(ps, q, side=side).astype(jnp.int32)

    def less(a, b):                              # lexicographic a < b
        ne = (a != b).astype(jnp.int32)
        tied_before = jnp.cumsum(ne, axis=-1) - ne == 0
        return jnp.any((a < b) & tied_before, axis=-1)

    P = ps.shape[0]
    lo = jnp.zeros(q.shape[:1], jnp.int32)
    hi = jnp.full(q.shape[:1], P, jnp.int32)
    for _ in range(P.bit_length()):
        mid = (lo + hi) // 2
        p = ps[jnp.minimum(mid, P - 1)]
        below = less(p, q) if side == "left" else less(p, q) | _equal(p, q)
        right = (lo < hi) & below
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    return lo


def match_points(x: jax.Array, pts: jax.Array) -> jax.Array:
    """alive-agnostic point match: out[...] = 1[x[...] ∈ set(pts)].

    x: [k, mloc] int points or [k, mloc, F] feature rows;
    pts: [P] or [P, F] (need not be deduplicated).
    """
    ps = pts[_sort_order(pts)]
    q = x.reshape((-1,) + x.shape[2:])
    lo = jnp.minimum(_search(ps, q, "left"), ps.shape[0] - 1)
    return _equal(ps[lo], q).reshape(x.shape[:2])


def distinct_count(pts: jax.Array) -> jax.Array:
    """|unique(pts)| as a traced int32 (first-occurrence counting) —
    the all-valid case of :func:`distinct_count_masked`, kept as one
    implementation so the two can never diverge."""
    return distinct_count_masked(pts, jnp.ones((pts.shape[0],), bool))


def _sentinel(dtype) -> jax.Array:
    """A value no real point can equal under sorting: +inf for floats,
    dtype max for ints (outside every [0, n) domain)."""
    return (jnp.asarray(jnp.inf, dtype)
            if jnp.issubdtype(dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(dtype).max, dtype))


def mask_invalid_points(pts: jax.Array, valid: jax.Array) -> jax.Array:
    """Replace entries where ``valid`` is False so they can never match
    a real point (``match_points``-safe sentinel: scalar points → the
    sorting sentinel; float rows → NaN, never ==)."""
    if pts.ndim == 2:
        if jnp.issubdtype(pts.dtype, jnp.floating):
            return jnp.where(valid[:, None], pts, jnp.nan)
        return jnp.where(valid[:, None], pts, _sentinel(pts.dtype))
    return jnp.where(valid, pts, _sentinel(pts.dtype))


def distinct_count_masked(pts: jax.Array, valid: jax.Array) -> jax.Array:
    """|unique(pts[valid])| as a traced int32.

    The all-valid case is bit-identical to :func:`distinct_count` — the
    fault-tolerant engines call this with the per-round player mask so a
    dropped player's (untransmitted) coreset rows never inflate the
    dispute-table size P.
    """
    if pts.ndim == 2:
        eq = jnp.all(pts[:, None, :] == pts[None], axis=-1)     # [P, P]
        eq = eq & valid[None, :] & valid[:, None]
        earlier = jnp.tril(eq, k=-1)
        first = valid & ~jnp.any(earlier, axis=-1)
        return jnp.sum(first.astype(jnp.int32))
    big = _sentinel(pts.dtype)
    ps = jnp.sort(jnp.where(valid, pts, big))
    bumps = jnp.concatenate(
        [jnp.ones((1,), bool), ps[1:] != ps[:-1]])
    return jnp.sum((bumps & (ps != big)).astype(jnp.int32))


def dispute_table(x: np.ndarray, y: np.ndarray, alive0: np.ndarray,
                  disputed: np.ndarray):
    """Host-side: (unique points, n₊, n₋) from a disputed-example mask.

    Because quarantine always removes *every* copy of a disputed point,
    the copies of a point alive at its quarantine time are exactly its
    initially-alive copies — so the D-table counts are reconstructible
    from the mask alone, independent of attempt order.  Points with zero
    alive copies under ``alive0`` (e.g. every copy lived at a player
    masked out of the table) carry no label evidence and are dropped —
    the ensemble decides there, matching the host loop's zero-support
    filter.
    """
    x, y = np.asarray(x), np.asarray(y)
    alive0, disputed = np.asarray(alive0), np.asarray(disputed)
    sel = disputed.reshape(-1)
    if x.ndim == 3:
        flat = x.reshape(-1, x.shape[-1])
        pts = np.unique(flat[sel], axis=0) if sel.any() else \
            np.zeros((0, x.shape[-1]), x.dtype)
    else:
        flat = x.reshape(-1)
        pts = np.unique(flat[sel])
    pos, neg = _point_counts(x, y, alive0, pts)
    keep = (pos + neg) > 0
    return pts[keep], pos[keep], neg[keep]


def point_index(points: np.ndarray, pts: np.ndarray) -> np.ndarray:
    """Host-side: for each of ``points`` ([M] or [M, F]) the index of an
    entry of ``pts`` equal to it, −1 where there is none.
    ``np.unique`` groups by ``==`` (a NaN equals nothing), in
    O((M+P)·log(M+P)) where an all-pairs compare is O(M·P)."""
    _, inv = np.unique(np.concatenate([pts, points]), axis=0,
                       return_inverse=True, equal_nan=False)
    inv = inv.reshape(-1)
    of_group = np.full(inv.max() + 1, -1, np.int64)
    of_group[inv[:len(pts)]] = np.arange(len(pts))
    return of_group[inv[len(pts):]]


def _point_index(x: np.ndarray, pts: np.ndarray) -> np.ndarray:
    """:func:`point_index` of the points of shards x [k, mloc(, F)]."""
    return point_index(x.reshape((-1,) + pts.shape[1:]),
                       pts).reshape(x.shape[:2])


def _kill_points(x: np.ndarray, alive: np.ndarray, pts: np.ndarray):
    """Remove every copy of every disputed point, on every player."""
    return alive & (_point_index(x, pts) < 0)


def _point_counts(x: np.ndarray, y: np.ndarray, alive: np.ndarray,
                  pts: np.ndarray):
    """Label counts of each disputed point (``pts`` distinct) over all
    (alive) copies in S."""
    idx = _point_index(x, pts).reshape(-1)
    yf = y.reshape(-1)
    live = (idx >= 0) & alive.reshape(-1)
    return (np.bincount(idx[live & (yf > 0)], minlength=len(pts)),
            np.bincount(idx[live & (yf < 0)], minlength=len(pts)))


def _emit_attempt(sp, att_led: Ledger, res, q_control: int,
                  q_dispute: int) -> None:
    """Annotate a host attempt span with its per-category wire bits —
    the attempt's Theorem 4.1 ledger delta plus the quarantine charges
    — in the ``task_bits`` format ``repro.obs.roundtrace``'s validator
    sums (the host engine is single-task: everything lands on task 0).
    """
    bits = obs_trace.ledger_bits(att_led)
    bits["control"] += q_control
    bits["quarantine"] += q_dispute
    sp.update(task_bits={"0": bits},
              task_rounds={"0": res.rounds + (1 if res.stuck else 0)},
              task_attempts={"0": 1},
              rounds=res.rounds, stuck=res.stuck)


def run_accurately_classify(x, y, key, cfg: BoostConfig, cls,
                            alive=None) -> ClassifyResult:
    """Host-driven outer loop (≤ opt_budget BoostAttempt calls).

    x, y: [k, m_loc] shards (int-domain track) or [k, m_loc, F] features.
    """
    x_np, y_np = np.asarray(x), np.asarray(y)
    k, mloc = x_np.shape[0], x_np.shape[1]
    if alive is None:
        alive_np = np.ones((k, mloc), bool)
    else:
        alive_np = np.asarray(alive)
    led = Ledger()
    dis_pts: list = []
    dis_pos: list = []
    dis_neg: list = []
    stuck_history = []
    result = None
    m_bits_m = max(int(np.ceil(np.log2(max(k * mloc, 2)))), 1)
    n = L.domain_size(cls)
    for _attempt in range(cfg.opt_budget + 1):
        with obs_trace.span("attempt", "protocol", engine="host",
                            attempt=_attempt) as att_sp:
            key, sub = jax.random.split(key)
            m_alive = int(alive_np.sum())
            res = boost_attempt.run_boost_attempt(
                jnp.asarray(x_np), jnp.asarray(y_np),
                jnp.asarray(alive_np), sub, cfg, cls)
            att_led = L.boost_attempt_ledger(cfg, cls, max(m_alive, 2),
                                             res.rounds, res.stuck)
            led = led + att_led
            stuck_history.append(res.stuck)
            if not res.stuck:
                result = res
                if obs_trace.enabled():
                    _emit_attempt(att_sp, att_led, res, 0, 0)
                break
            # ---- full-point quarantine of the non-realizable coreset
            with obs_trace.span("quarantine", "protocol",
                                attempt=_attempt):
                cx = np.asarray(res.coreset_x).reshape(
                    (-1,) + tuple(np.asarray(res.coreset_x).shape[2:]))
                pts = (np.unique(cx, axis=0) if cx.ndim == 2
                       else np.unique(cx))
                pos, neg = _point_counts(x_np, y_np, alive_np, pts)
                # A coreset from a fully-dead shard can name points
                # with zero alive copies (repeat-disputed or
                # initially-padded).  They carry no label evidence, so
                # they don't enter the D-table / classifier vote (the
                # ensemble decides there) — this keeps f identical to
                # the mask-based batched engine.  The broadcast still
                # happened, so the ledger below charges the full |pts|.
                keep = (pos + neg) > 0
                dis_pts.append(pts[keep])
                dis_pos.append(pos[keep])
                dis_neg.append(neg[keep])
                alive_np = _kill_points(x_np, alive_np, pts)
                # ledger: point-set broadcast + per-player count reports
                P = int(pts.shape[0])
                q_control = cfg.k * P * L.point_bits(n)       # broadcast
                q_dispute = cfg.k * P * 2 * m_bits_m          # counts up
                led.bits_control += q_control
                led.bits_dispute += q_dispute
            if obs_trace.enabled():
                _emit_attempt(att_sp, att_led, res, q_control, q_dispute)
    if result is None:
        raise RuntimeError(
            f"AccuratelyClassify exceeded opt_budget={cfg.opt_budget}; "
            "OPT is larger than the promise this run was configured for.")
    if dis_pts:
        dpts = np.concatenate(dis_pts)
        dpos = np.concatenate(dis_pos)
        dneg = np.concatenate(dis_neg)
    else:
        dpts = np.zeros((0,) + tuple(x_np.shape[2:]), x_np.dtype)
        dpos = np.zeros((0,), np.int64)
        dneg = np.zeros((0,), np.int64)
    return ClassifyResult(
        hypotheses=result.hypotheses, rounds=result.rounds,
        dispute_x=jnp.asarray(dpts),
        dispute_y=(jnp.asarray(dpos), jnp.asarray(dneg)),
        dispute_count=int(dpts.shape[0]),
        attempts=len(stuck_history), stuck_history=stuck_history,
        ledger=led)


@dataclasses.dataclass(frozen=True)
class ResilientClassifier:
    """The final classifier f — dispute-vote patched over the ensemble.

    ``dispute_pos/neg`` are full label counts of each disputed point in
    S, so the vote is the pointwise-optimal labelling.
    """

    cls: object
    hypotheses: jax.Array        # [T, 4]
    rounds: int
    dispute_x: jax.Array         # [P] or [P, F]
    dispute_pos: jax.Array       # [P]
    dispute_neg: jax.Array       # [P]

    def g(self, x: jax.Array) -> jax.Array:
        return weak.ensemble_predict(self.cls, self.hypotheses,
                                     self.rounds, x)

    def __call__(self, x: jax.Array) -> jax.Array:
        gx = self.g(x).astype(jnp.int32)
        if self.dispute_x.shape[0] == 0:
            return gx.astype(jnp.int8)
        # counts summed over every dispute entry equal to x: the entries
        # [lo, hi) of the sorted table, via prefix sums
        order = _sort_order(self.dispute_x)
        ps = self.dispute_x[order]

        def prefix(c):
            return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(c[order].astype(jnp.int32))])

        lead = x.shape[:x.ndim - (ps.ndim - 1)]
        q = x.reshape((-1,) + ps.shape[1:])
        lo = _search(ps, q, "left")
        hi = _search(ps, q, "right")
        in_d = (hi > lo) & _equal(ps[jnp.minimum(lo, ps.shape[0] - 1)], q)
        cpos, cneg = prefix(self.dispute_pos), prefix(self.dispute_neg)
        vote = jnp.where(cpos[hi] - cpos[lo] >= cneg[hi] - cneg[lo], 1, -1)
        out = jnp.where(in_d.reshape(lead), vote.reshape(lead), gx)
        return out.astype(jnp.int8)


def make_classifier(cls, result: ClassifyResult) -> ResilientClassifier:
    pos, neg = result.dispute_y
    return ResilientClassifier(
        cls=cls, hypotheses=result.hypotheses, rounds=result.rounds,
        dispute_x=result.dispute_x, dispute_pos=pos, dispute_neg=neg)


def learn(x, y, key, cfg: BoostConfig, cls):
    """One-call API: returns (classifier, ClassifyResult)."""
    result = run_accurately_classify(x, y, key, cfg, cls)
    return make_classifier(cls, result), result
