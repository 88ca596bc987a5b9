"""BoostAttempt (Figure 1) — distributed boosting that may get "stuck".

Two executable forms of the same round body:

* :func:`run_boost_attempt` — single-process simulation.  The k players
  are the leading axis of the sample arrays; player-local steps are
  ``vmap``-ed over that axis and the "center" runs inline.  This is the
  reference used by tests/benchmarks and the communication-ledger
  validation (the ledger charges exactly what *would* cross the wire).

* :func:`boost_attempt_sharded` — ``shard_map`` over the mesh ``data``
  (× ``pod``) axis: each device group is one player; the coresets and
  the scalar weight sums are ``all_gather``-ed (the star topology's
  k → center messages), the center's weighted ERM runs replicated, and
  the multiplicative-weights update is purely local.  This is what the
  production launcher and the multi-pod dry-run lower.

The loop is a ``jax.lax.while_loop`` with the paper's termination:
either T = ⌈6·log2 m⌉ hypotheses were produced (boosting succeeded,
Lemma 4.2 ⇒ E_S(f) = 0 on the alive sample) or the center certifies
that no hypothesis has mixture loss ≤ 1/100 (stuck ⇒ the pooled coreset
is non-realizable, Observation 4.3).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import approximation, weights as W
from repro.core import streaming, weak
from repro.core.types import BoostAttemptResult, BoostConfig
from repro.obs import trace as obs_trace


class _Carry(NamedTuple):
    t: jax.Array            # hypotheses produced so far
    it: jax.Array           # loop iterations (rounds attempted)
    stuck: jax.Array        # bool
    hits: jax.Array         # [k, mloc] int32
    key: jax.Array
    h_params: jax.Array     # [T, 4]
    core_idx: jax.Array     # [k, c] last-round coreset (local indices)
    core_x: jax.Array       # [k, c(, F)]
    core_y: jax.Array       # [k, c]
    min_loss: jax.Array     # last center ERM loss


def _gather_coreset(x, y, idx):
    take = functools.partial(jnp.take_along_axis, axis=1)
    if x.ndim == 3:  # feature track: [k, mloc, F]
        cx = take(x, idx[..., None])
    else:
        cx = take(x, idx)
    return cx, take(y, idx)


def step_scope(step: str):
    """The ``jax.named_scope`` of one protocol step of the round body.

    ``step`` is one of ``repro.obs.trace.ROUND_STEPS``.  A named scope
    is device-side metadata (no ops, no host work): the compiler keeps
    it in each instruction's ``op_name``, where
    ``repro.obs.trace.op_steps`` reads it back.  It is NOT an obs
    emission, so RL006 permits it in traced code."""
    if step not in obs_trace.ROUND_STEPS:
        raise ValueError(f"unknown round step {step!r}")
    return jax.named_scope(step)


def _center_erm(cls, cx, cy, mix, c):
    """Pooled-coreset ERM under the mixture D_t (step 2(c)+(d)).

    Classes with a distributed ``comm_mode`` (weak_tree trees in
    histogram/voting mode) grow from per-player partials instead: here
    the caller already holds all k players' shards, so the per-player
    grower runs with an identity gather — the same float path the
    sharded engine's real collectives produce (bit-parity per mode).
    """
    k = cy.shape[0]
    with step_scope("weight_sums"):
        pw = W.erm_weights(mix, c)
    with step_scope("center_erm"):
        if getattr(cls, "comm_mode", "coreset") != "coreset":
            return cls.erm_players(cx, cy, pw)
        w = jnp.broadcast_to(pw[:, None], (k, c)).reshape(-1)
        cx_flat = cx.reshape((k * c,) + cx.shape[2:])
        cy_flat = cy.reshape(-1)
        return cls.erm(cx_flat, cy_flat, w)


def _round_body(cfg: BoostConfig, cls, x, y, alive, x_orders,
                y_sorted, alive_sorted, carry: _Carry, *,
                player_alive=None) -> _Carry:
    # --- players: step 2(a) coreset + step 2(b) weight sums -------------
    with step_scope("coreset_draw"):
        key, kc = jax.random.split(carry.key)
        keys = jax.random.split(kc, x.shape[0])
        idx = jax.vmap(
            lambda kk, xx, yy, hh, aa, oo, yso, aso:
            approximation.select_coreset(
                kk, xx if xx.ndim == 1 else xx[:, 0], yy, hh, aa,
                cfg.coreset_size,
                cfg.deterministic_coreset and x.ndim == 2,
                order=oo, y_sorted=yso, alive_sorted=aso)
        )(keys, x, y, carry.hits, alive, x_orders, y_sorted, alive_sorted)
        cx, cy = _gather_coreset(x, y, idx)
    with step_scope("weight_sums"):
        log_wsums = jax.vmap(W.log_weight_sum)(carry.hits, alive)  # [k]
        if player_alive is not None:
            # a player absent this round sends nothing: its weight sum
            # is excluded from the mixture (−inf ⇒ mixture weight 0, so
            # its coreset entries carry zero weight in the center ERM —
            # the candidate behaviours they add are sound: zero-weight
            # points can only certify MORE hypotheses, never hide a
            # good one)
            log_wsums = jnp.where(player_alive, log_wsums, -jnp.inf)
        mix = W.mixture_weights(log_wsums)
    # --- center: step 2(c)+(d) weighted ERM over the pooled coreset -----
    h, loss = _center_erm(cls, cx, cy, mix, cfg.coreset_size)
    # --- players: step 2(f) multiplicative-weights update ---------------
    with step_scope("predict"):
        pred = cls.predict(h, x)
    with step_scope("mw_update"):
        stuck_now = loss > cfg.weak_threshold
        correct = (pred == y)
        upd = W.update_hits(carry.hits, correct, alive)
        if player_alive is not None:
            # absent players never received h_t: their MW state freezes
            upd = jnp.where(player_alive[:, None], upd, carry.hits)
        new_hits = jnp.where(stuck_now, carry.hits, upd)
        h_params = carry.h_params.at[carry.t].set(
            jnp.where(stuck_now, carry.h_params[carry.t], h))
        t = jnp.where(stuck_now, carry.t, carry.t + 1)
    return _Carry(
        t=t,
        it=carry.it + 1,
        stuck=stuck_now,
        hits=new_hits,
        key=key,
        h_params=h_params,
        core_idx=idx, core_x=cx, core_y=cy,
        min_loss=loss,
    )


def boost_attempt_arrays(x, y, alive, hits0, key, cfg: BoostConfig, cls,
                         num_rounds: int, *, round_bound=None,
                         x_orders=None):
    """Jittable BoostAttempt core. Returns the final carry tuple.

    ``num_rounds`` is the *static* hypothesis-buffer size.  The loop
    itself stops at ``round_bound`` when given (a traced int32 ≤
    ``num_rounds``) — this is what lets the batched engine run the
    paper's T = ⌈6·log2 m_alive⌉ bound with a per-task, per-attempt
    alive count while keeping one fixed-shape program.  ``x_orders``
    optionally passes in the loop-invariant per-player argsort so an
    outer loop (AccuratelyClassify attempts) can hoist it.
    """
    k, c = x.shape[0], cfg.coreset_size
    carry = _Carry(
        t=jnp.int32(0), it=jnp.int32(0), stuck=jnp.asarray(False),
        hits=hits0, key=key,
        h_params=jnp.zeros((num_rounds, weak.param_dim(cls)),
                           jnp.float32),
        core_idx=jnp.zeros((k, c), jnp.int32),
        core_x=jnp.zeros((k, c) + x.shape[2:], x.dtype),
        core_y=jnp.zeros((k, c), y.dtype),
        min_loss=jnp.float32(0),
    )
    bound = num_rounds if round_bound is None else round_bound

    def cond(cy: _Carry):
        return (~cy.stuck) & (cy.t < bound)

    # §Perf P1: loop-invariant per-player argsort hoisted out of the
    # round loop; §Perf P4: so are the y/alive gathers into sorted space.
    # With cfg.chunk_size the order is built from chunk-local sorted
    # runs (streaming tier) — bitwise identical, never sorts > a chunk.
    with step_scope("sort_order"):
        if x_orders is None:
            x1d = x if x.ndim == 2 else x[:, :, 0]
            x_orders = jax.vmap(lambda v: streaming.sort_order(
                v, cfg.chunk_size, cfg.domain_size))(x1d)
        y_sorted = jnp.take_along_axis(y, x_orders, axis=1)
        alive_sorted = jnp.take_along_axis(alive, x_orders, axis=1)
    return jax.lax.while_loop(
        cond, functools.partial(_round_body, cfg, cls, x, y, alive,
                                x_orders, y_sorted, alive_sorted), carry)


@functools.partial(jax.jit, static_argnames=("cfg", "cls", "num_rounds"))
def _boost_attempt_jit(x, y, alive, hits0, key, cfg, cls, num_rounds):
    return boost_attempt_arrays(x, y, alive, hits0, key, cfg, cls,
                                num_rounds)


def run_boost_attempt(x, y, alive, key, cfg: BoostConfig,
                      cls) -> BoostAttemptResult:
    """Host-facing single-process BoostAttempt on [k, mloc] shards."""
    m = int(jnp.sum(alive)) if not isinstance(alive, bool) else x.size
    num_rounds = cfg.num_rounds(max(m, 2))
    hits0 = W.init_hits(x.shape[:2])
    with obs_trace.span("boost_attempt", "attempt", m_alive=m,
                        bound=num_rounds) as sp:
        out = _boost_attempt_jit(x, y, alive, hits0, key, cfg, cls,
                                 num_rounds)
        out = jax.device_get(out)
        if obs_trace.enabled():
            sp.update(rounds=int(out.t), stuck=bool(out.stuck))
    return BoostAttemptResult(
        stuck=bool(out.stuck), rounds=int(out.t),
        hypotheses=out.h_params,
        coreset_index=out.core_idx, coreset_x=out.core_x,
        coreset_y=out.core_y, min_mixture_loss=float(out.min_loss))


# ---------------------------------------------------------------------------
# shard_map production form: one player per device group along `data` axis.
# ---------------------------------------------------------------------------

def boost_attempt_sharded(mesh, cfg: BoostConfig, cls, num_rounds: int,
                          player_axes=("data",), no_center: bool = False):
    """Build the sharded BoostAttempt step.

    Returns a function (x, y, alive, hits, key) -> final carry where
    x/y/alive/hits are sharded [m_total(, F)] along ``player_axes`` and
    every device holds the replicated protocol outputs.  The coreset
    all_gather is the only cross-player communication per round — this
    IS the paper's message pattern on the wire.

    ``no_center=True`` implements the paper's §2.2 no-center model:
    player 0 plays the center — the coresets converge to it with a
    masked gather (psum of one-hot-placed contributions ≡ k→1 messages
    on a star-less topology), it alone runs the weak-learner ERM, and
    the chosen hypothesis is broadcast back (psum from player 0).  The
    default (False) emulates the center by an all_gather + replicated
    ERM, which is bit-equivalent on the wire model (every player
    receives the same coresets the center would).
    """
    axes = player_axes

    def per_device(x, y, alive, hits, key):
        # local shard plays one player; reconstruct the [1, mloc] layout
        xl = x[None]
        yl, al, hl = y[None], alive[None], hits[None]
        # §Perf P1: the domain points are loop-invariant — sort once
        # outside the round loop instead of inside every coreset build
        # (chunk-local runs under cfg.chunk_size, bitwise identical).
        x1d = xl[0] if xl.ndim == 2 else xl[0, :, 0]
        with step_scope("sort_order"):
            x_order = (streaming.sort_order(x1d, cfg.chunk_size,
                                            cfg.domain_size)
                       if cfg.deterministic_coreset else None)
            y_sorted = yl[0][x_order] if x_order is not None else None
            alive_sorted = (al[0][x_order] if x_order is not None
                            else None)

        def round_body(carry):
            t, it, stuck, hitsl, kkey, h_params, last_loss = carry
            pid = jax.lax.axis_index(axes)
            with step_scope("coreset_draw"):
                kkey, kc = jax.random.split(kkey)
                # identical key on all players is fine: sampling uses
                # the per-player fold below.
                kp = jax.random.fold_in(kc, pid)
                idx = approximation.select_coreset(
                    kp, x1d, yl[0],
                    hitsl[0], al[0], cfg.coreset_size,
                    cfg.deterministic_coreset and xl.ndim == 2,
                    order=x_order, y_sorted=y_sorted,
                    alive_sorted=alive_sorted)
                cx, cy = _gather_coreset(xl, yl, idx[None])
                # the wire: gather tiny coresets + one scalar per player
                cx_all = jax.lax.all_gather(cx[0], axes, tiled=False)
                cy_all = jax.lax.all_gather(cy[0], axes, tiled=False)
            with step_scope("weight_sums"):
                log_wsum = W.log_weight_sum(hitsl[0], al[0])
                ws_all = jax.lax.all_gather(log_wsum, axes, tiled=False)
            if isinstance(axes, tuple) and len(axes) > 1:
                cx_all = cx_all.reshape((-1,) + cx_all.shape[2:])
                cy_all = cy_all.reshape((-1,) + cy_all.shape[2:])
                ws_all = ws_all.reshape(-1)
            with step_scope("weight_sums"):
                mix = W.mixture_weights(ws_all)
            if no_center:
                # Only player 0 (the acting center) runs the ERM; the
                # result is then broadcast from it.  lax.cond keeps the
                # non-center players' lane idle (the compiler still
                # schedules SPMD-uniformly, but the broadcast makes the
                # center's answer authoritative bit-for-bit).
                h0, loss0 = jax.lax.cond(
                    pid == 0,
                    lambda: _center_erm(cls, cx_all, cy_all, mix,
                                        cfg.coreset_size),
                    lambda: (jnp.zeros((weak.param_dim(cls),),
                                       jnp.float32),
                             jnp.float32(0)))
                h = jax.lax.psum(jnp.where(pid == 0, h0, 0.0), axes)
                loss = jax.lax.psum(jnp.where(pid == 0, loss0, 0.0),
                                    axes)
            else:
                h, loss = _center_erm(cls, cx_all, cy_all, mix,
                                      cfg.coreset_size)
            with step_scope("predict"):
                pred = cls.predict(h, xl)
            with step_scope("mw_update"):
                stuck_now = loss > cfg.weak_threshold
                new_hits = jnp.where(
                    stuck_now, hitsl,
                    W.update_hits(hitsl, pred == yl, al))
                h_params = h_params.at[t].set(
                    jnp.where(stuck_now, h_params[t], h))
            return (jnp.where(stuck_now, t, t + 1), it + 1, stuck_now,
                    new_hits, kkey, h_params, loss)

        def cond(carry):
            t, it, stuck = carry[0], carry[1], carry[2]
            return (~stuck) & (t < num_rounds)

        carry0 = (jnp.int32(0), jnp.int32(0), jnp.asarray(False), hl, key,
                  jnp.zeros((num_rounds, weak.param_dim(cls)),
                            jnp.float32),
                  jnp.float32(0))
        t, it, stuck, hitsl, _, h_params, loss = jax.lax.while_loop(
            cond, round_body, carry0)
        return t, stuck, hitsl[0], h_params, loss

    in_specs = (P(*axes), P(*axes), P(*axes), P(*axes), P())
    out_specs = (P(), P(), P(*axes), P(), P())
    return jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
