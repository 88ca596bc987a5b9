"""Fault-tolerant protocol execution (ISSUE 4 tentpole).

Three layers, each pinned bitwise:

* **Stepping API.**  ``init_state / run_rounds / finalize`` run in
  slices is bit-identical to the uninterrupted engine run (which is
  itself bit-identical to the host reference loop — tests/test_batched
  keeps that anchor).  A round slice crosses attempt boundaries.
* **Checkpoint/resume.**  The whole protocol state round-trips through
  a msgpack file (ckpt/msgpack_ckpt) mid-run and completes identically.
* **Infrastructure adversaries.**  dropout / flaky / rejoin player
  schedules: the protocol proceeds with k′ < k players, E_S(f) ≤ OPT
  holds over the surviving shards, the sharded engine stays bit-equal
  to the local one under the same schedule, and ``validate_ledger``
  passes with the mask applied — only alive players' payloads charged.
"""

import os

import jax
import numpy as np
import pytest

from repro.core import (batched, ledger, scenarios, sharded_batched,
                        tasks, weak)
from repro.ckpt import msgpack_ckpt
from repro.core.types import BoostConfig

N = 1 << 12
CFG = BoostConfig(k=4, coreset_size=100, domain_size=N, opt_budget=16)
CLS = weak.Thresholds(n=N)


def _batch(B=2, m=512, noise=3, seed0=11):
    x, y, ts = tasks.make_batch(CLS, B, m, 4, noise, seed0=seed0)
    keys = jax.random.split(jax.random.key(5), B)
    return x, y, keys, ts


def _assert_bitwise(ref, got):
    np.testing.assert_array_equal(ref.hypotheses, got.hypotheses)
    np.testing.assert_array_equal(ref.rounds, got.rounds)
    np.testing.assert_array_equal(ref.ok, got.ok)
    np.testing.assert_array_equal(ref.attempts, got.attempts)
    np.testing.assert_array_equal(ref.alive, got.alive)
    np.testing.assert_array_equal(ref.disputed, got.disputed)
    np.testing.assert_array_equal(ref.hist_stuck, got.hist_stuck)
    np.testing.assert_array_equal(ref.hist_rounds, got.hist_rounds)
    np.testing.assert_array_equal(ref.hist_alive, got.hist_alive)
    np.testing.assert_array_equal(ref.hist_p, got.hist_p)
    np.testing.assert_array_equal(ref.hist_players, got.hist_players)
    np.testing.assert_array_equal(ref.hist_players_h,
                                  got.hist_players_h)
    np.testing.assert_array_equal(ref.hist_players_last,
                                  got.hist_players_last)
    for b in range(ref.batch):
        for f in ("bits_coresets", "bits_weight_sums", "bits_hypotheses",
                  "bits_control", "bits_dispute", "bits_histograms",
                  "bits_votes", "rounds", "attempts"):
            assert getattr(ref.ledger(b), f) == getattr(got.ledger(b), f), f


# ---------------------------------------------------------------------------
# Round-granular stepping ≡ monolithic run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slice_rounds", [1, 3, 7])
def test_sliced_run_rounds_bit_identical(slice_rounds):
    x, y, keys, _ = _batch()
    full = batched.run_accurately_classify_batched(x, y, keys, CFG, CLS)
    state = batched.init_state(x, y, keys, CFG)
    a_max = CFG.opt_budget + 1
    slices = 0
    while bool(np.any(~np.asarray(state.done)
                      & (np.asarray(state.attempt) < a_max))):
        state = batched.run_rounds(state, x, y, CFG, CLS,
                                   n=slice_rounds)
        slices += 1
        assert slices < 500, "stepper failed to terminate"
    got = batched.finalize(state, x, y, full.alive0, CFG, CLS)
    assert slices > 1            # the slicing actually sliced
    _assert_bitwise(full, got)


def test_stepper_feature_track_randomized_coreset():
    """Slicing must preserve the PRNG stream of the randomized-coreset
    (AxisStumps) track too — keys are state, not recomputed."""
    cls = weak.AxisStumps(num_features=4)
    cfg = BoostConfig(k=2, coreset_size=64, domain_size=N, opt_budget=8,
                      deterministic_coreset=False)
    x, y, _ = tasks.make_batch(cls, 2, 128, 2, 1, seed0=3)
    keys = jax.random.split(jax.random.key(9), 2)
    full = batched.run_accurately_classify_batched(x, y, keys, cfg, cls)
    state = batched.init_state(x, y, keys, cfg)
    for _ in range(200):
        state = batched.run_rounds(state, x, y, cfg, cls, n=2)
        if bool(np.all(np.asarray(state.done))):
            break
    got = batched.finalize(state, x, y, full.alive0, cfg, cls)
    _assert_bitwise(full, got)


def test_checkpoint_resume_bit_identical(tmp_path):
    """Protocol state → msgpack file → fresh process state → resume:
    the completed run equals the uninterrupted one, bit for bit."""
    x, y, keys, _ = _batch()
    full = batched.run_accurately_classify_batched(x, y, keys, CFG, CLS)
    state = batched.run_rounds(batched.init_state(x, y, keys, CFG),
                               x, y, CFG, CLS, n=4)
    path = os.path.join(tmp_path, "engine_state.msgpack")
    msgpack_ckpt.save_pytree(path, jax.device_get(state),
                             meta={"rounds_done": 4})
    del state                                   # the preemption
    template = batched.init_state(x, y, keys, CFG)
    restored, meta = msgpack_ckpt.load_pytree(path, like=template)
    assert meta["rounds_done"] == 4
    done = batched.run_rounds(restored, x, y, CFG, CLS)
    got = batched.finalize(done, x, y, full.alive0, CFG, CLS)
    _assert_bitwise(full, got)


def test_sharded_checkpoint_resume_bit_identical(tmp_path):
    x, y, keys, _ = _batch()
    full = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, CFG, CLS)
    state = sharded_batched.init_state_sharded(x, y, keys, CFG)
    state = sharded_batched.run_rounds_sharded(state, x, y, CFG, CLS,
                                               n=5)
    path = os.path.join(tmp_path, "sharded_state.msgpack")
    msgpack_ckpt.save_pytree(path, jax.device_get(state), meta={})
    del state
    template = sharded_batched.init_state_sharded(x, y, keys, CFG)
    restored, _ = msgpack_ckpt.load_pytree(path, like=template)
    done = sharded_batched.run_rounds_sharded(restored, x, y, CFG, CLS)
    got = sharded_batched.finalize_sharded(done, x, y, full.alive0,
                                           CFG, CLS)
    _assert_bitwise(full, got)
    for b in range(full.batch):
        got.validate_ledger(b)


# ---------------------------------------------------------------------------
# Infrastructure adversaries
# ---------------------------------------------------------------------------

SPECS = {
    "dropout": scenarios.InfraSpec(name="dropout", player=1,
                                   drop_round=5),
    "flaky": scenarios.InfraSpec(name="flaky", player=2, miss_rate=0.3,
                                 horizon=64),
    "rejoin": scenarios.InfraSpec(name="rejoin", player=0, drop_round=4,
                                  rejoin_round=12),
}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_infra_adversary_guarantee_over_survivors(name):
    """The protocol proceeds with k′ < k players and E_S(f) ≤ OPT holds
    over the surviving shards (the pinned per-adversary guarantee)."""
    spec = SPECS[name]
    sched = spec.schedule(4, seed=0)
    assert not sched.all(), "adversary must actually silence someone"
    x, y, keys, ts = _batch(B=3)
    res = batched.run_accurately_classify_batched(
        x, y, keys, CFG, CLS, player_sched=sched)
    assert bool(res.ok.all())
    for b in range(3):
        rep = scenarios.infra_report(ts[b], res, b, spec)
        assert rep["guarantee_ok"], (name, b, rep)
    # determinism: same schedule, same bits
    res2 = batched.run_accurately_classify_batched(
        x, y, keys, CFG, CLS, player_sched=sched)
    _assert_bitwise(res, res2)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_infra_ledger_equals_payload_under_mask(name):
    """Sharded engine under the same schedule: bit-equal to the local
    engine, and Theorem 4.1 accounting == measured collective payloads
    with only alive players' messages charged."""
    spec = SPECS[name]
    sched = spec.schedule(4, seed=0)
    x, y, keys, _ = _batch(B=2)
    ref = batched.run_accurately_classify_batched(
        x, y, keys, CFG, CLS, player_sched=sched)
    got = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, CFG, CLS, player_sched=sched)
    _assert_bitwise(ref, got)
    baseline = batched.run_accurately_classify_batched(x, y, keys, CFG,
                                                       CLS)
    for b in range(2):
        got.validate_ledger(b)
        # masked accounting is strictly cheaper than all-alive on the
        # rounds the silenced player missed
        assert got.ledger(b).total_bits < baseline.ledger(b).total_bits
        k_dead_rounds = int(np.sum(~sched.all(axis=-1)))
        assert k_dead_rounds > 0
        # the per-attempt alive-player sums never exceed k·wire_rounds
        n_att = int(got.attempts[b])
        for a in range(n_att):
            wire = int(got.hist_rounds[b, a]) + int(got.hist_stuck[b, a])
            assert int(got.hist_players[b, a]) <= wire * CFG.k


def test_dropout_quarantine_excludes_dead_players_coreset():
    """A stuck round after the dropout must quarantine only points the
    ALIVE players' coresets named — the dead player's rows are masked
    out of the match and the dispute-table size P."""
    spec = scenarios.InfraSpec(name="dropout", player=1, drop_round=0)
    sched = spec.schedule(4, seed=0)       # player 1 never participates
    x, y, keys, _ = _batch(B=2)
    res = batched.run_accurately_classify_batched(
        x, y, keys, CFG, CLS, player_sched=sched)
    assert bool(res.ok.all())
    for b in range(2):
        if not res.disputed[b].any():
            continue
        # every disputed point must occur in some surviving player's
        # shard (the dead player's shard alone can't name points)
        disputed_pts = np.unique(res.x[b][res.disputed[b]])
        surv_pts = np.unique(res.x[b][[0, 2, 3]])
        assert np.isin(disputed_pts, surv_pts).all()


def test_player_schedule_shapes_and_validation():
    spec = scenarios.InfraSpec(name="dropout", player=2, drop_round=3)
    sched = spec.schedule(4)
    assert sched.shape == (4, 4)
    np.testing.assert_array_equal(sched[:3, 2], True)
    assert not sched[3, 2]
    np.testing.assert_array_equal(spec.survivors(4),
                                  [True, True, False, True])
    rj = scenarios.InfraSpec(name="rejoin", player=0, drop_round=2,
                             rejoin_round=5)
    s = rj.schedule(3)
    np.testing.assert_array_equal(s[:, 0],
                                  [True, True, False, False, False, True])
    assert rj.survivors(3).all()
    fl = scenarios.InfraSpec(name="flaky", player=1, miss_rate=0.5,
                             horizon=32)
    s = fl.schedule(2, seed=3)
    assert s.shape == (32, 2) and s[:, 0].all() and s[-1, 1]
    assert not s[:, 1].all()               # it actually missed rounds
    assert fl.survivors(2, seed=3).all()
    with pytest.raises(ValueError):
        scenarios.InfraSpec(name="warp-core-breach")
    with pytest.raises(ValueError):
        scenarios.InfraSpec(name="rejoin", drop_round=5, rejoin_round=5)
    with pytest.raises(ValueError):
        scenarios.InfraSpec(name="dropout").schedule(1)   # k=1: nobody left
    assert scenarios.InfraSpec(name="none").schedule(1).shape == (1, 1)


def test_masked_point_helpers_int_and_float():
    """mask_invalid_points / distinct_count_masked work on every point
    dtype the tracks use — 1-D int, 1-D float, and float feature rows —
    and the all-valid case equals the unmasked count."""
    import jax.numpy as jnp

    from repro.core import classify

    pts_i = jnp.asarray([5, 5, 2, 9], jnp.int32)
    valid = jnp.asarray([True, True, False, True])
    assert int(classify.distinct_count_masked(pts_i, valid)) == 2
    assert int(classify.distinct_count(pts_i)) == 3
    masked = classify.mask_invalid_points(pts_i, valid)
    assert not bool(classify.match_points(
        jnp.asarray([[2]], jnp.int32), masked)[0, 0])
    pts_f = jnp.asarray([1.5, 2.5, 1.5], jnp.float32)
    assert int(classify.distinct_count(pts_f)) == 2
    assert int(classify.distinct_count_masked(
        pts_f, jnp.asarray([True, False, True]))) == 1
    rows = jnp.asarray([[1.0, 2.0], [3.0, 4.0]], jnp.float32)
    rv = jnp.asarray([True, False])
    assert int(classify.distinct_count_masked(rows, rv)) == 1
    mrows = classify.mask_invalid_points(rows, rv)
    assert not bool(classify.match_points(rows[None, 1:2], mrows)[0, 0])


@pytest.mark.parametrize("F", [0, 1, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_point_lookups_equal_brute_force(seed, F):
    """The sorted point lookups — device match, host quarantine and
    label counts, the classifier's dispute vote — equal the all-pairs
    ``==`` compare on int points (F = 0) and on float rows with shared
    prefixes, duplicates, −0.0 against +0.0, masked (NaN) rows and rows
    holding one NaN."""
    import jax.numpy as jnp

    from repro.core import classify

    rng = np.random.default_rng(seed)
    k, mloc, P = 3, 40, 33
    if F:
        x = rng.integers(0, 3, (k, mloc, F)).astype(np.float32) / 2
        x[0, :5, 0] = -0.0
        x[1, 0, F - 1] = np.nan
    else:
        x = rng.integers(0, 60, (k, mloc)).astype(np.int32)
    flat = x.reshape((k * mloc,) + x.shape[2:])
    pts = flat[rng.choice(k * mloc, P)]
    pts[:3] = (rng.integers(0, 3, (3, F)) / 2 if F
               else rng.integers(60, 63, 3))            # absent points
    if F:
        pts[3, 0] = np.nan
    valid = rng.random(P) > 0.2
    masked = np.asarray(classify.mask_invalid_points(jnp.asarray(pts),
                                                     jnp.asarray(valid)))

    def brute(a, b):                                   # [len a, len b]
        eq = a.reshape(len(a), 1, -1) == b.reshape(1, len(b), -1)
        return eq.all(-1)

    eq = brute(flat, masked)
    assert eq.any() and not eq.any(-1).all()
    got = classify.match_points(jnp.asarray(x), jnp.asarray(masked))
    np.testing.assert_array_equal(np.asarray(got),
                                  eq.any(-1).reshape(k, mloc))

    uniq = np.unique(pts, axis=0)
    alive = rng.random((k, mloc)) > 0.3
    y = rng.choice(np.array([-1, 1], np.int8), (k, mloc))
    eq = brute(flat, uniq)
    np.testing.assert_array_equal(
        classify._kill_points(x, alive, uniq),
        alive & ~eq.any(-1).reshape(k, mloc))
    pos, neg = classify._point_counts(x, y, alive, uniq)
    live = alive.reshape(-1, 1) & eq
    np.testing.assert_array_equal(pos, (live & (y.reshape(-1, 1) > 0)).sum(0))
    np.testing.assert_array_equal(neg, (live & (y.reshape(-1, 1) < 0)).sum(0))
    np.testing.assert_array_equal(
        scenarios.quarantine_recall(uniq, flat),
        brute(flat, uniq).any(-1).mean())

    # points carrying both labels in S
    eq = brute(flat, flat)
    yf = y.reshape(-1)
    both = (eq & (yf > 0)).any(-1) & (eq & (yf < 0)).any(-1)
    task = tasks.Task(x=x, y=y, target_params=None, noise_count=0, cls=None)
    got = scenarios.contradicted_points(task)
    np.testing.assert_array_equal(got, np.unique(flat[both], axis=0))
    assert 0 < len(got)

    # the vote sums counts over repeated dispute entries
    dx = np.concatenate([uniq, uniq[:4]])
    dpos = rng.integers(0, 4, len(dx))
    dneg = rng.integers(0, 4, len(dx))
    cls = weak.make_class("tree", num_features=max(F, 1)) if F else \
        weak.make_class("thresholds", n=64)
    f = classify.ResilientClassifier(
        cls=cls, hypotheses=jnp.zeros((1, weak.param_dim(cls)), jnp.float32),
        rounds=0, dispute_x=jnp.asarray(dx), dispute_pos=jnp.asarray(dpos),
        dispute_neg=jnp.asarray(dneg))
    eq = brute(flat, dx)
    want = np.where(eq.any(-1),
                    np.where(eq @ dpos >= eq @ dneg, 1, -1),
                    np.asarray(f.g(jnp.asarray(flat))))
    np.testing.assert_array_equal(np.asarray(f(jnp.asarray(x))),
                                  want.reshape(k, mloc))


def test_canon_player_sched_rejects_dead_rounds():
    with pytest.raises(ValueError):
        batched.canon_player_sched(np.zeros((2, 4), bool), B=1, k=4)
    with pytest.raises(ValueError):
        batched.canon_player_sched(np.ones((1, 3), bool), B=1, k=4)
    out = batched.canon_player_sched(np.ones((2, 4), bool), B=3, k=4)
    assert out.shape == (3, 2, 4)


def test_checkpoint_shape_mismatch_fails_loudly(tmp_path):
    """Restoring engine state against a template of different shapes
    (wrong batch / budget) must raise a clear error, not a reshape
    failure inside a jit trace."""
    x, y, keys, _ = _batch(B=2, m=256)
    state = batched.run_rounds(batched.init_state(x, y, keys, CFG),
                               x, y, CFG, CLS, n=2)
    path = os.path.join(tmp_path, "state.msgpack")
    msgpack_ckpt.save_pytree(path, jax.device_get(state), meta={})
    x3, y3, keys3, _ = _batch(B=3, m=256)
    wrong = batched.init_state(x3, y3, keys3, CFG)
    with pytest.raises(ValueError, match="shape"):
        msgpack_ckpt.load_pytree(path, like=wrong)


# ---------------------------------------------------------------------------
# Distributed tree-growth modes (histogram-merge / voting) under
# infrastructure adversaries — dead players must contribute neither
# histograms nor votes, and the masked ledger must still equal the
# measured collective payloads.
# ---------------------------------------------------------------------------

TREE_CFG = BoostConfig(k=4, coreset_size=64, domain_size=1 << 12,
                       opt_budget=16, deterministic_coreset=False)
TREE_SPECS = {
    "dropout": scenarios.InfraSpec(name="dropout", player=1,
                                   drop_round=0),
    "rejoin": scenarios.InfraSpec(name="rejoin", player=0, drop_round=2,
                                  rejoin_round=5),
}


def _tree_cls(mode):
    return weak.make_class("tree", num_features=4, tree_depth=2,
                           tree_bins=8, tree_comm_mode=mode,
                           tree_vote_topk=1)


def _tree_batch(cls, B=2, m=256, seed0=21):
    spec = scenarios.ScenarioSpec(name="xor", noise=2)
    x, y, ts = scenarios.make_scenario_batch(cls, B, m, 4, spec,
                                             seed0=seed0)
    keys = jax.random.split(jax.random.key(7), B)
    return x, y, keys, ts


@pytest.mark.parametrize("infra", sorted(TREE_SPECS))
@pytest.mark.parametrize("mode", ["histogram", "voting"])
def test_tree_comm_infra_parity_and_masked_ledger(mode, infra):
    """Batched ≡ sharded bitwise under dropout/rejoin for both
    distributed tree-growth modes, with validate_ledger proving the
    masked accounting equals the measured histogram/vote payloads."""
    cls = _tree_cls(mode)
    sched = TREE_SPECS[infra].schedule(4, seed=0)
    assert not sched.all()
    x, y, keys, _ = _tree_batch(cls)
    ref = batched.run_accurately_classify_batched(
        x, y, keys, TREE_CFG, cls, player_sched=sched)
    got = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, TREE_CFG, cls, player_sched=sched)
    assert bool(ref.ok.all())
    _assert_bitwise(ref, got)
    wire = np.asarray(got.hist_rounds) + np.asarray(got.hist_stuck)
    alive_rounds = np.asarray(got.hist_players)
    assert np.any(alive_rounds < 4 * wire)   # somebody actually missed
    for b in range(ref.batch):
        got.validate_ledger(b)               # masked ledger ≡ payload
        led = got.ledger(b)
        assert led.bits_histograms > 0       # both modes merge hists
        assert (led.bits_votes > 0) == (mode == "voting")


def test_tree_comm_dead_player_ships_no_payload():
    """With player 1 silenced for the whole run, the measured histogram
    and vote payload counters can only ever count 3 alive players per
    wire round — the dead player's messages are never charged."""
    cls = _tree_cls("voting")
    sched = TREE_SPECS["dropout"].schedule(4, seed=0)
    x, y, keys, _ = _tree_batch(cls)
    got = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, TREE_CFG, cls, player_sched=sched)
    assert bool(got.ok.all())
    wire = np.asarray(got.hist_rounds) + np.asarray(got.hist_stuck)
    assert np.all(np.asarray(got.hist_players) <= 3 * wire)
    hist_pp = ledger.hist_scalars_per_player(cls)
    vote_pp = ledger.vote_entries_per_player(cls)
    assert hist_pp > 0 and vote_pp > 0
    for b in range(got.batch):
        got.validate_ledger(b)
        # the measured counters are exactly (alive player-rounds) ×
        # (static per-player payload): 3/4 of the all-alive charge
        n_att = int(got.attempts[b])
        pr = int(np.sum(np.asarray(got.hist_players)[b, :n_att]))
        assert int(np.sum(got.hist_wire_hist[b, :n_att])) \
            == pr * hist_pp
        assert int(np.sum(got.hist_wire_votes[b, :n_att])) \
            == pr * vote_pp


@pytest.mark.parametrize("mode", ["histogram", "voting"])
def test_tree_comm_sharded_checkpoint_resume(mode, tmp_path):
    """Mid-run sharded state → msgpack (template-free restore) → resume:
    bit-identical to the uninterrupted run for both distributed modes —
    the new histogram/vote wire counters round-trip with the state."""
    cls = _tree_cls(mode)
    x, y, keys, _ = _tree_batch(cls)
    full = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, TREE_CFG, cls)
    state = sharded_batched.init_state_sharded(x, y, keys, TREE_CFG,
                                               cls=cls)
    state = sharded_batched.run_rounds_sharded(state, x, y, TREE_CFG,
                                               cls, n=3)
    path = os.path.join(tmp_path, f"tree_{mode}.msgpack")
    msgpack_ckpt.save_pytree(path, jax.device_get(state),
                             treedef=sharded_batched.STATE_TREEDEF)
    del state                                    # the preemption
    restored, _ = msgpack_ckpt.restore_pytree(path)
    assert {"awire_hist", "awire_votes",
            "hist_wire_hist", "hist_wire_votes"} <= set(restored)
    done = sharded_batched.run_rounds_sharded(restored, x, y, TREE_CFG,
                                              cls)
    got = sharded_batched.finalize_sharded(done, x, y, full.alive0,
                                           TREE_CFG, cls)
    _assert_bitwise(full, got)
    np.testing.assert_array_equal(full.hist_wire_hist,
                                  got.hist_wire_hist)
    np.testing.assert_array_equal(full.hist_wire_votes,
                                  got.hist_wire_votes)
    for b in range(full.batch):
        got.validate_ledger(b)


def test_all_alive_schedule_is_a_bitwise_noop():
    """An explicit all-alive schedule must not perturb a single bit
    relative to the default path (masking reduces exactly)."""
    x, y, keys, _ = _batch(B=2)
    ref = batched.run_accurately_classify_batched(x, y, keys, CFG, CLS)
    got = batched.run_accurately_classify_batched(
        x, y, keys, CFG, CLS, player_sched=np.ones((7, 4), bool))
    _assert_bitwise(ref, got)
