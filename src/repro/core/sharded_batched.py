"""Mesh-sharded batched AccuratelyClassify — k players as device shards.

`core/batched.py` runs B tasks in one jitted program, but it still
*simulates* the k players inside a single device: the "coreset
transmission" of step 2(a) is a vmap lane, not a message.  This module
runs the identical protocol over a real device mesh with a ``players``
axis: each device holds only its players' shards of every task, the
per-round coreset and weight-sum exchange is an actual
``lax.all_gather`` (the star topology's k → center messages), the alive
count is a ``lax.psum``, and the §2.2 no-center variant broadcasts the
acting center's hypothesis back with a ``psum`` — so the bytes the
communication ledger charges correspond to payloads that really cross
device boundaries.

Like the local engine, execution is **round-granular**
(:func:`init_state_sharded` / :func:`run_rounds_sharded` /
:func:`finalize_sharded`): one step is one BoostAttempt wire round,
attempt transitions happen inside the step body, and the state is a
plain dict of arrays — host-gatherable and msgpack-serializable, so a
preempted run resumes bit-identically from a checkpoint.  A per-round
``player_alive [k]`` schedule drives the infrastructure adversaries
(dropout / flaky / rejoin): an absent player's weight sum leaves the
mixture, its MW state freezes, its coreset rows are excluded from
quarantine, and — because the wire counters below are masked at the
collective sites — the ledger charges only payloads alive players
actually sent.

Two properties are load-bearing and tested (tests/test_sharded_batched):

* **Bit-identical parity.**  Given the same per-task keys and schedule,
  every output (hypotheses, quarantine masks, stuck/round/alive
  histories, ledger bit counts) equals `core/batched.py`'s exactly.
  This holds by construction: the per-player steps (coreset selection,
  weight sums, MW updates) touch only local rows, the pooled arrays
  entering the center ERM are reassembled in player order by the
  all_gather, and integer/float op order is unchanged — a player living
  on another device computes the same row it computed as a vmap lane.

* **Ledger ≡ payload.**  The engine counts, *at the collective sites*,
  how many coreset examples and weight-sum scalars each attempt
  gathered from players alive that round.  ``validate_ledger`` then
  checks the Theorem 4.1 accounting against those measured counts:
  ledger coreset bits = gathered examples × ``example_bits(n)``, ledger
  weight-sum bits = per-attempt gathered scalars ×
  ``weight_sum_bits(m_alive, T)``, quarantine messages = k_alive·P per
  stuck attempt.  The accounting is validated by construction, not by
  trust — with or without a dropout mask.

The mesh's ``players`` axis size p must divide k; each device then
hosts kloc = k/p players (p = k is one player per device).  On a
single-device host the same program runs with p = 1 — the collectives
still execute (over an axis of size 1), so the wire accounting and the
program structure are identical, only the transport is trivial.  Use
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to simulate an
N-device CPU mesh (see TESTING.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.ckpt import msgpack_ckpt
from repro.core import approximation, batched, ledger as L
from repro.core import streaming, weak
from repro.core import weights as W
from repro.core.pinned import pinned_argmax
from repro.core.boost_attempt import _center_erm, _gather_coreset
from repro.core.boost_attempt import step_scope
from repro.core.types import BoostConfig
from repro.obs import trace as obs_trace

AXIS = "players"


def make_players_mesh(k: int, devices=None) -> Mesh:
    """A 1-axis ``players`` mesh of p devices, p = the largest divisor
    of k the host can supply (p = 1 degenerates to the local engine,
    p = k is one player per device)."""
    devices = list(jax.devices() if devices is None else devices)
    p = max(d for d in range(1, min(k, len(devices)) + 1) if k % d == 0)
    return Mesh(np.asarray(devices[:p]), (AXIS,))


class _RoundCarry(NamedTuple):
    t: jax.Array            # hypotheses produced so far
    it: jax.Array           # loop iterations (wire rounds)
    stuck: jax.Array
    hits: jax.Array         # [kloc, mloc] — local players only
    key: jax.Array
    h_params: jax.Array     # [t_buf, 4] replicated
    core_x: jax.Array       # [k, c(, F)] pooled coreset (all_gather output)
    core_y: jax.Array       # [k, c]
    min_loss: jax.Array
    wire_core: jax.Array    # int32 — coreset examples gathered this attempt
    wire_ws: jax.Array      # int32 — weight-sum scalars gathered this attempt
    wire_bytes: jax.Array   # int32 — machine bytes of those collectives
    wire_hist: jax.Array    # int32 — histogram scalars merged (comm_mode)
    wire_votes: jax.Array   # int32 — vote proposals exchanged (voting)


def _slice_player_keys(keys_all: jax.Array, kloc: int) -> jax.Array:
    """This device's kloc keys out of the k per-player keys."""
    pid = jax.lax.axis_index(AXIS)
    return jax.lax.dynamic_slice_in_dim(keys_all, pid * kloc, kloc, axis=0)


def _local_player_mask(player_alive: jax.Array, kloc: int) -> jax.Array:
    """This device's kloc entries of the replicated [k] player mask."""
    pid = jax.lax.axis_index(AXIS)
    return jax.lax.dynamic_slice_in_dim(player_alive, pid * kloc, kloc)


def _round_body(cfg: BoostConfig, cls, k: int, x, y, alive, x_orders,
                y_sorted, alive_sorted, no_center: bool,
                c: _RoundCarry, *, player_alive=None) -> _RoundCarry:
    # LOCKSTEP: this is boost_attempt._round_body with the vmap-lane
    # pooling replaced by collectives (and _one_step_sharded below
    # mirrors batched._one_step the same way).  Any semantic change to
    # the round/step bodies there must land here too — the exact-parity
    # tests (tests/test_sharded_batched.py) fail on any divergence.
    kloc = x.shape[0]
    # --- players (local rows only): step 2(a) coreset + 2(b) sums; the
    # wire: every alive player's coreset + one scalar each --------------
    with step_scope("coreset_draw"):
        key, kc = jax.random.split(c.key)
        keys_all = jax.random.split(kc, k)  # the host loop's k-key stream
        keys = _slice_player_keys(keys_all, kloc)
        idx = jax.vmap(
            lambda kk, xx, yy, hh, aa, oo, yso, aso:
            approximation.select_coreset(
                kk, xx if xx.ndim == 1 else xx[:, 0], yy, hh, aa,
                cfg.coreset_size,
                cfg.deterministic_coreset and x.ndim == 2,
                order=oo, y_sorted=yso, alive_sorted=aso)
        )(keys, x, y, c.hits, alive, x_orders, y_sorted, alive_sorted)
        cx, cy = _gather_coreset(x, y, idx)               # [kloc, c(, F)]
        cx_all = jax.lax.all_gather(cx, AXIS)         # [p, kloc, c(, F)]
        cy_all = jax.lax.all_gather(cy, AXIS)
        cx_all = cx_all.reshape((k,) + cx_all.shape[2:])  # player order
        cy_all = cy_all.reshape((k,) + cy_all.shape[2:])
    with step_scope("weight_sums"):
        log_wsums = jax.vmap(W.log_weight_sum)(c.hits, alive)  # [kloc]
        if player_alive is not None:
            # an absent player sends nothing: its weight sum leaves the
            # mixture before the gather (−inf ⇒ mixture weight 0)
            log_wsums = jnp.where(_local_player_mask(player_alive, kloc),
                                  log_wsums, -jnp.inf)
        ws_all = jax.lax.all_gather(log_wsums, AXIS).reshape(-1)   # [k]
        mix = W.mixture_weights(ws_all)
    comm_mode = L.tree_comm_mode(cls)
    # payload counters: what alive players actually sent.  Unmasked,
    # they are taken from the gathered arrays themselves (move iff the
    # collective executed, by its actual size); masked, they charge the
    # per-player payload × the round's alive count.
    with step_scope("lane_state"):
        k_alive = (jnp.int32(k) if player_alive is None
                   else jnp.sum(player_alive.astype(jnp.int32)))
        core_pp_bytes = ((cx_all.size // k) * cx_all.dtype.itemsize
                         + (cy_all.size // k) * cy_all.dtype.itemsize)
        if comm_mode == "coreset":
            if player_alive is None:
                n_examples = int(np.prod(cy_all.shape))   # k · c, exactly
                n_scalars = int(np.prod(ws_all.shape))    # k
                n_bytes = (cx_all.size * cx_all.dtype.itemsize
                           + cy_all.size * cy_all.dtype.itemsize
                           + ws_all.size * ws_all.dtype.itemsize)
            else:
                n_examples = k_alive * cfg.coreset_size
                n_scalars = k_alive
                n_bytes = k_alive * (core_pp_bytes
                                     + ws_all.dtype.itemsize)
    # --- center: step 2(c)+(d) pooled weighted ERM ----------------------
    if comm_mode != "coreset":
        # Distributed tree growth: split finding runs on per-player
        # histograms (and votes), merged by a REAL collective — the
        # every-round coreset gather above survives only as a carry-
        # shape/quarantine simulation artifact; protocol-wise examples
        # cross the wire solely on the stuck round, and the counters
        # below charge exactly that.  The merge is centerless by
        # construction (every device computes the identical merged
        # answer), so the §2.2 no_center flag is moot here.
        with step_scope("weight_sums"):
            pid = jax.lax.axis_index(AXIS)
            mix_loc = jax.lax.dynamic_slice_in_dim(mix, pid * kloc, kloc,
                                                   0)
            pw = W.erm_weights(mix_loc, cfg.coreset_size)

        def _ag(a):
            g = jax.lax.all_gather(a, AXIS)
            return g.reshape((k,) + g.shape[2:])

        with step_scope("center_erm"):
            h, loss = cls.erm_players(cx, cy, pw, all_gather=_ag)
    elif no_center:
        # §2.2: the first ALIVE player acts as center; only its device
        # runs the ERM and the result is psum-broadcast back (exact:
        # all other summands are literal zeros).
        with step_scope("center_erm"):
            pid = jax.lax.axis_index(AXIS)
            center = (jnp.int32(0) if player_alive is None
                      else pinned_argmax(player_alive))
            cdev = center // kloc
            h0, loss0 = jax.lax.cond(
                pid == cdev,
                lambda: _center_erm(cls, cx_all, cy_all, mix,
                                    cfg.coreset_size),
                lambda: (jnp.zeros((weak.param_dim(cls),), jnp.float32),
                         jnp.float32(0)))
            h = jax.lax.psum(jnp.where(pid == cdev, h0, 0.0), AXIS)
            loss = jax.lax.psum(jnp.where(pid == cdev, loss0, 0.0), AXIS)
    else:
        h, loss = _center_erm(cls, cx_all, cy_all, mix, cfg.coreset_size)
    with step_scope("mw_update"):
        stuck_now = loss > cfg.weak_threshold
    with step_scope("lane_state"):
        if comm_mode != "coreset":
            # distributed-mode payloads: per-player scalar counts are
            # STATIC class properties (ledger.py charges the same
            # formulas) × the round's alive-player count; coreset
            # examples move only when this round sticks (quarantine
            # ships the points then)
            hist_pp = L.hist_scalars_per_player(cls)
            vote_pp = L.vote_entries_per_player(cls)
            n_examples = jnp.where(stuck_now,
                                   k_alive * cfg.coreset_size, 0)
            n_scalars = k_alive
            n_hist = k_alive * hist_pp
            n_votes = k_alive * vote_pp
            n_bytes = (jnp.where(stuck_now, k_alive * core_pp_bytes, 0)
                       + k_alive * (ws_all.dtype.itemsize
                                    + 4 * hist_pp   # f32 histogram cells
                                    + 4 * vote_pp))  # i32 vote entries
        else:
            n_hist = jnp.int32(0)
            n_votes = jnp.int32(0)
    # --- players: step 2(f) multiplicative-weights update (local) ------
    with step_scope("predict"):
        pred = cls.predict(h, x)
    with step_scope("mw_update"):
        upd = W.update_hits(c.hits, pred == y, alive)
        if player_alive is not None:
            # absent players never received h_t: their MW state freezes
            upd = jnp.where(
                _local_player_mask(player_alive, kloc)[:, None], upd,
                c.hits)
        new_hits = jnp.where(stuck_now, c.hits, upd)
        h_params = c.h_params.at[c.t].set(
            jnp.where(stuck_now, c.h_params[c.t], h))
        t = jnp.where(stuck_now, c.t, c.t + 1)
    return _RoundCarry(
        t=t,
        it=c.it + 1,
        stuck=stuck_now,
        hits=new_hits,
        key=key,
        h_params=h_params,
        core_x=cx_all, core_y=cy_all,
        min_loss=loss,
        wire_core=c.wire_core + n_examples,
        wire_ws=c.wire_ws + n_scalars,
        wire_bytes=c.wire_bytes + n_bytes,
        wire_hist=c.wire_hist + n_hist,
        wire_votes=c.wire_votes + n_votes,
    )


# ---------------------------------------------------------------------------
# Round-granular stepping over the mesh.  The per-task state is a plain
# dict of arrays: {alive, disputed, hits} are player-sharded, the rest
# replicated — host-gathered it checkpoints via ckpt/msgpack_ckpt.
# ---------------------------------------------------------------------------

_SHARDED_FIELDS = ("alive", "disputed", "hits")

# -- checkpoint identity ----------------------------------------------------
# The sharded state is the batched StepState's leaves (same names, same
# dtypes — built by batched.init_state) plus the wire-payload counters.

STATE_TREEDEF = "repro.core.sharded_batched.state"

STATE_DTYPES = dict(
    batched.STATE_DTYPES,
    awire_core="int32", awire_ws="int32", hist_wire_core="int32",
    hist_wire_ws="int32", wire_bytes="int32", wire_q_points="int32",
    wire_q_counts="int32", awire_hist="int32", awire_votes="int32",
    hist_wire_hist="int32", hist_wire_votes="int32")


def _unflatten_state(leaves: dict) -> dict:
    missing = set(STATE_DTYPES) - set(leaves)
    if missing:
        raise KeyError(f"checkpoint missing sharded-state leaves: "
                       f"{sorted(missing)}")
    batched.check_state_dtypes(leaves, STATE_DTYPES, "sharded state")
    return dict(leaves)


msgpack_ckpt.register_treedef(STATE_TREEDEF, _unflatten_state)


def init_state_sharded(x, y, keys, cfg: BoostConfig, alive=None,
                       t_buf: int | None = None, cls=None) -> dict:
    """Fresh sharded-engine state (global [B, …] arrays; the shard_map
    call partitions the player-sharded fields per its in_specs).

    Same input shapes/dtypes as ``batched.init_state``: ``x``
    [B, k, mloc] int32 or [B, k, mloc, F] float32, ``y`` [B, k, mloc]
    int8 ±1, ``keys`` [B] PRNG keys, ``alive`` optional [B, k, mloc]
    bool.  Returns a dict state: the protocol fields ARE
    ``batched.init_state``'s — built by it, so the two engines' state
    layouts (and checkpoint shape contracts) can never drift — plus
    int32 [B] / [B, A] wire-payload counters (gathered coreset
    examples, weight-sum scalars, histogram scalars, vote proposals,
    collective bytes) that only this engine maintains.  ``cls`` sizes
    the ensemble buffers, exactly as there.  Bitwise contract: the
    protocol fields evolve identically to the local batched engine's
    on any mesh shape (docs/architecture.md,
    tests/test_sharded_batched.py); the counters feed
    ``ShardedClassifyResult.validate_ledger`` (docs/ledger.md).
    """
    state = batched.init_state(jnp.asarray(x), jnp.asarray(y), keys,
                               cfg, alive=alive, t_buf=t_buf,
                               cls=cls)._asdict()
    B = state["attempt"].shape[0]
    a_max = cfg.opt_budget + 1
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    state.update(
        awire_core=i32((B,)), awire_ws=i32((B,)),
        hist_wire_core=i32((B, a_max)),
        hist_wire_ws=i32((B, a_max)),
        wire_bytes=i32((B,)),
        wire_q_points=i32((B,)), wire_q_counts=i32((B,)),
        awire_hist=i32((B,)), awire_votes=i32((B,)),
        hist_wire_hist=i32((B, a_max)),
        hist_wire_votes=i32((B, a_max)))
    return state


def _one_step_sharded(cfg: BoostConfig, cls, k: int, no_center: bool,
                      x, y, x_orders, sched, s: dict) -> dict:
    """ONE wire round of ONE task on this device's [kloc, mloc] shard.
    LOCKSTEP with batched._one_step (collectives replace lane pooling)."""
    a_max = cfg.opt_budget + 1
    kloc = x.shape[0]
    with step_scope("lane_state"):
        active = (~s["done"]) & (s["attempt"] < a_max)
        pa = sched[jnp.minimum(s["step"], sched.shape[0] - 1)]       # [k]
        pa_loc = _local_player_mask(pa, kloc)
        # ---- attempt start ------------------------------------------------
        start = ~s["in_attempt"]
        tkey = jax.random.wrap_key_data(s["key_data"])
        nk, sub = jax.random.split(tkey)
        key_data = jnp.where(start, jax.random.key_data(nk), s["key_data"])
        akey_data = jnp.where(start, jax.random.key_data(sub),
                              s["akey_data"])
        m_alive = jax.lax.psum(
            jnp.sum((s["alive"] & pa_loc[:, None]).astype(jnp.int32)), AXIS)
        a = s["attempt"]
        bound = jnp.where(start, batched.num_rounds_dynamic(cfg, m_alive),
                          s["bound"])
        hits = jnp.where(start, W.init_hits(x.shape[:2]), s["hits"])
        cur_h = jnp.where(start, jnp.zeros_like(s["cur_h"]), s["cur_h"])
        t = jnp.where(start, 0, s["t"])
        awire_core = jnp.where(start, 0, s["awire_core"])
        awire_ws = jnp.where(start, 0, s["awire_ws"])
        awire_hist = jnp.where(start, 0, s["awire_hist"])
        awire_votes = jnp.where(start, 0, s["awire_votes"])
        hist_alive = jnp.where(start, s["hist_alive"].at[a].set(m_alive),
                               s["hist_alive"])
    # ---- one BoostAttempt round over the wire -------------------------
    with step_scope("sort_order"):
        y_sorted = jnp.take_along_axis(y, x_orders, axis=1)
        alive_sorted = jnp.take_along_axis(s["alive"], x_orders, axis=1)
    rc = _RoundCarry(
        t=t, it=jnp.int32(0), stuck=jnp.asarray(False),
        hits=hits, key=jax.random.wrap_key_data(akey_data),
        h_params=cur_h, core_x=s["core_x"], core_y=s["core_y"],
        min_loss=s["min_loss"],
        wire_core=jnp.int32(0), wire_ws=jnp.int32(0),
        wire_bytes=jnp.int32(0), wire_hist=jnp.int32(0),
        wire_votes=jnp.int32(0))
    out = _round_body(cfg, cls, k, x, y, s["alive"], x_orders, y_sorted,
                      alive_sorted, no_center, rc, player_alive=pa)
    with step_scope("lane_state"):
        stuck = out.stuck
        success = (~stuck) & (out.t >= bound)
        ended = stuck | success
        k_alive = jnp.sum(pa.astype(jnp.int32))
        awire_core = awire_core + out.wire_core
        awire_ws = awire_ws + out.wire_ws
        awire_hist = awire_hist + out.wire_hist
        awire_votes = awire_votes + out.wire_votes
        nxt = {
            "attempt": jnp.where(ended, a + 1, a),
            "done": s["done"] | success,
            "alive": s["alive"],
            "disputed": s["disputed"],
            "key_data": key_data,
            "h_params": jnp.where(success, out.h_params, s["h_params"]),
            "rounds": jnp.where(success, out.t, s["rounds"]),
            "min_loss": out.min_loss,
            "hist_stuck": jnp.where(ended, s["hist_stuck"].at[a].set(stuck),
                                    s["hist_stuck"]),
            "hist_rounds": jnp.where(ended,
                                     s["hist_rounds"].at[a].set(out.t),
                                     s["hist_rounds"]),
            "hist_alive": hist_alive,
            "hist_p": s["hist_p"],
            "hist_players": s["hist_players"].at[a].add(k_alive),
            "hist_players_h": s["hist_players_h"].at[a].add(
                jnp.where(stuck, 0, k_alive)),
            "hist_players_last": s["hist_players_last"].at[a].set(k_alive),
            "in_attempt": ~ended,
            "akey_data": jax.random.key_data(out.key),
            "t": out.t,
            "bound": bound,
            "hits": out.hits,
            "cur_h": out.h_params,
            "core_x": out.core_x, "core_y": out.core_y,
            "step": s["step"] + 1,
            "awire_core": awire_core, "awire_ws": awire_ws,
            "awire_hist": awire_hist, "awire_votes": awire_votes,
            "hist_wire_core": jnp.where(
                ended, s["hist_wire_core"].at[a].set(awire_core),
                s["hist_wire_core"]),
            "hist_wire_ws": jnp.where(
                ended, s["hist_wire_ws"].at[a].set(awire_ws),
                s["hist_wire_ws"]),
            "hist_wire_hist": jnp.where(
                ended, s["hist_wire_hist"].at[a].set(awire_hist),
                s["hist_wire_hist"]),
            "hist_wire_votes": jnp.where(
                ended, s["hist_wire_votes"].at[a].set(awire_votes),
                s["hist_wire_votes"]),
            "wire_bytes": s["wire_bytes"] + out.wire_bytes,
            "wire_q_points": s["wire_q_points"],
            "wire_q_counts": s["wire_q_counts"],
        }
        nxt = jax.tree_util.tree_map(
            lambda new, old: jnp.where(active, new, old), nxt, s)
    return nxt, batched.Stuck(stuck & active, out.core_x, pa)


def _quarantine_lane(cfg: BoostConfig, x, s: dict,
                     st: batched.Stuck) -> dict:
    """Full-point quarantine on this device's shard: the pooled stuck
    coreset is replicated (it is the all_gather output); dead players'
    rows are masked out and each device kills its local copies; every
    sender then reports its P counts."""
    dead_new, p_count = batched.quarantine(cfg, x, s["alive"], st)
    k_alive = jnp.sum(st.senders.astype(jnp.int32))
    return {**s,
            "alive": s["alive"] & ~dead_new,
            "disputed": s["disputed"] | dead_new,
            "hist_p": jnp.where(
                st.stuck, s["hist_p"].at[s["attempt"] - 1].set(p_count),
                s["hist_p"]),
            "wire_q_points": s["wire_q_points"] + k_alive * p_count,
            "wire_q_counts": s["wire_q_counts"] + k_alive * p_count}


@functools.lru_cache(maxsize=None)
def _build_sharded_step(mesh: Mesh, cfg: BoostConfig, cls,
                        no_center: bool):
    """jitted shard_map program (x, y, sched, state, n) → state."""
    k = cfg.k
    p = mesh.shape[AXIS]
    if k % p != 0:
        raise ValueError(f"players mesh size {p} must divide k={k}")
    a_max = cfg.opt_budget + 1

    def per_device(x, y, sched, state, n):
        x1d = x if x.ndim == 3 else x[..., 0]
        # chunk-local runs under cfg.chunk_size, bitwise identical to
        # the monolithic argsort (streaming tier)
        with step_scope("sort_order"):
            x_orders = jax.vmap(jax.vmap(lambda v: streaming.sort_order(
                v, cfg.chunk_size, cfg.domain_size)))(x1d)

        def active(st):
            return (~st["done"]) & (st["attempt"] < a_max)

        def cond(carry):
            st, i = carry
            with step_scope("lane_state"):
                return jnp.any(active(st)) & (i < n)

        def body(carry):
            st, i = carry
            st2, stuck = jax.vmap(functools.partial(
                _one_step_sharded, cfg, cls, k, no_center))(
                x, y, x_orders, sched, st)
            # the pooled loss is replicated, so every device takes the
            # same branch
            with step_scope("quarantine"):
                st2 = jax.lax.cond(
                    jnp.any(stuck.stuck),
                    lambda st2: jax.vmap(functools.partial(
                        _quarantine_lane, cfg))(x, st2, stuck),
                    lambda st2: st2, st2)
            with step_scope("lane_state"):
                return st2, i + 1

        out, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
        return out

    sharded = P(None, AXIS)
    state_specs = {f: (sharded if f in _SHARDED_FIELDS else P())
                   for f in init_state_sharded(
                       np.zeros((1, k, 2), np.int32),
                       np.zeros((1, k, 2), np.int8),
                       jax.random.split(jax.random.key(0), 1), cfg,
                       cls=cls)}
    in_specs = (sharded, sharded, P(), state_specs, P())
    return jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                                 out_specs=state_specs, check_vma=False))


def run_rounds_sharded(state: dict, x, y, cfg: BoostConfig, cls,
                       mesh: Mesh | None = None, n: int | None = None,
                       player_sched=None, no_center: bool = False) -> dict:
    """Advance the sharded protocol by up to ``n`` wire rounds (None =
    to completion); the mesh-collective twin of ``batched.run_rounds``.

    ``state``: the dict from :func:`init_state_sharded` (or a restored
    checkpoint); ``x``/``y``: the same [B, k, mloc(, F)] / [B, k, mloc]
    dispatch arrays; ``mesh``: a ``players`` mesh whose axis size
    divides k (default ``make_players_mesh(k)``); ``player_sched``:
    [R, k] / [B, R, k] bool infrastructure-adversary schedule;
    ``no_center``: the §2.2 center-free model.  Returns the advanced
    dict.  ``n`` is traced (one compiled program per signature, any
    slice size).  Bitwise contract: identical slicing ⇒ protocol
    fields identical to ``batched.run_rounds`` on the same inputs —
    the collectives change WHERE bytes move, never a single output
    bit — and ``cfg.chunk_size`` is equally invisible here
    (docs/streaming.md, tests/test_streaming.py)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    B, k = x.shape[0], x.shape[1]
    sched = batched.canon_player_sched(player_sched, B, k)
    if mesh is None:
        mesh = make_players_mesh(k)
    fn = _build_sharded_step(mesh, cfg, cls, no_center)
    n_arr = batched._RUN_FOREVER if n is None else jnp.int32(n)
    with obs_trace.span("run_rounds", "engine", engine="sharded", B=B,
                        n=(-1 if n is None else int(n)),
                        mesh_devices=int(mesh.shape[AXIS])):
        return fn(x, y, sched, state, n_arr)


@functools.lru_cache(maxsize=None)
def _build_sharded(mesh: Mesh, cfg: BoostConfig, cls, t_buf: int,
                   no_center: bool):
    """Full-run program (x, y, alive, keys, sched) → final state dict."""
    step = _build_sharded_step(mesh, cfg, cls, no_center)

    def full(x, y, alive, keys, sched):
        with step_scope("lane_state"):
            state = init_state_sharded(x, y, keys, cfg, alive=alive,
                                       t_buf=t_buf, cls=cls)
        return step(x, y, sched, state, batched._RUN_FOREVER)

    return jax.jit(full)


def lower_classify_sharded(x, y, alive, keys, cfg: BoostConfig, cls,
                           mesh: Mesh, no_center: bool = False,
                           player_sched=None):
    """AOT-compile the sharded engine for one input signature (the
    mesh-collective twin of ``batched.lower_classify``).  The returned
    executable is owned by the caller — a serving compile cache reuses
    it across admissions and dropping it really frees the program."""
    t_buf = cfg.num_rounds(x.shape[1] * x.shape[2])
    sched = batched.canon_player_sched(player_sched, x.shape[0],
                                       x.shape[1])
    fn = _build_sharded(mesh, cfg, cls, t_buf, no_center)
    with obs_trace.span("compile", "compile", engine="sharded",
                        B=int(x.shape[0]), mloc=int(x.shape[2])):
        return obs_trace.compile_published(
            fn, jnp.asarray(x), jnp.asarray(y), jnp.asarray(alive), keys,
            sched)


@dataclasses.dataclass
class ShardedClassifyResult(batched.BatchedClassifyResult):
    """BatchedClassifyResult + the measured collective payloads.

    ``per_task``, ``classifier`` and ``ledger`` are inherited unchanged
    (the protocol state is bit-identical to the local batched engine);
    the wire_* fields record what the collectives actually moved.
    """

    hist_wire_core: np.ndarray = None   # [B, A] coreset examples gathered
    hist_wire_ws: np.ndarray = None     # [B, A] weight-sum scalars gathered
    wire_bytes: np.ndarray = None       # [B] machine bytes of collectives
    wire_q_points: np.ndarray = None    # [B] quarantine point messages
    wire_q_counts: np.ndarray = None    # [B] quarantine count reports
    hist_wire_hist: np.ndarray = None   # [B, A] histogram scalars merged
    hist_wire_votes: np.ndarray = None  # [B, A] vote proposals exchanged
    mesh_devices: int = 1

    def wire_summary(self, b: int) -> dict:
        return {
            "coreset_examples": int(self.hist_wire_core[b].sum()),
            "weight_sum_scalars": int(self.hist_wire_ws[b].sum()),
            "histogram_scalars": int(self.hist_wire_hist[b].sum()),
            "vote_proposals": int(self.hist_wire_votes[b].sum()),
            "collective_bytes": int(self.wire_bytes[b]),
            "quarantine_point_msgs": int(self.wire_q_points[b]),
            "quarantine_count_msgs": int(self.wire_q_counts[b]),
            "mesh_devices": int(self.mesh_devices),
        }

    def validate_ledger(self, b: int) -> dict:
        """Cross-check Theorem 4.1 accounting against measured payloads.

        docs/ledger.md walks the accounting this validates, field by
        field, with a worked example and the masked variants.
        Raises AssertionError on any mismatch; returns the comparison.
        Checks, per task (all player-mask-aware — under a dropout
        schedule only alive players' payloads are charged):
        * ledger coreset bits == gathered examples × example_bits(n);
        * ledger weight-sum bits == Σ_attempts gathered scalars ×
          weight_sum_bits(m_alive, T) with per-attempt m_alive;
        * per attempt, gathered payload == Σ_rounds k_alive · c examples
          and Σ_rounds k_alive scalars (the protocol's message pattern);
          in a distributed comm_mode the per-round payload is instead
          Σ_rounds k_alive · hist_scalars (+ votes), with examples
          gathered only on the stuck round;
        * ledger histogram/vote bits == merged scalars / exchanged
          proposals × their per-attempt bit widths;
        * quarantine messages == Σ_stuck k_alive(stuck round) · P.
        """
        cfg, cls = self.cfg, self.cls
        n = L.domain_size(cls)
        mode = L.tree_comm_mode(cls)
        hist_pp = L.hist_scalars_per_player(cls)
        vote_pp = L.vote_entries_per_player(cls)
        led = self.ledger(b)
        n_att = int(self.attempts[b])
        got_core = int(self.hist_wire_core[b, :n_att].sum())
        got_ws = int(self.hist_wire_ws[b, :n_att].sum())
        exp_ws_bits = 0
        exp_hist_bits = 0
        exp_vote_bits = 0
        exp_q = 0
        for a in range(n_att):
            pl_rounds, _, pl_last = self._attempt_players(b, a)
            stuck = bool(self.hist_stuck[b, a])
            if mode == "coreset":
                assert int(self.hist_wire_core[b, a]) == \
                    pl_rounds * cfg.coreset_size, (b, a)
            else:
                # distributed modes gather examples only when stuck —
                # from the stuck round's alive players
                assert int(self.hist_wire_core[b, a]) == \
                    (pl_last * cfg.coreset_size if stuck else 0), (b, a)
            assert int(self.hist_wire_hist[b, a]) == \
                pl_rounds * hist_pp, (b, a)
            assert int(self.hist_wire_votes[b, a]) == \
                pl_rounds * vote_pp, (b, a)
            assert int(self.hist_wire_ws[b, a]) == pl_rounds, (b, a)
            m_a = max(int(self.hist_alive[b, a]), 2)
            T_a = cfg.num_rounds(m_a)
            exp_ws_bits += int(self.hist_wire_ws[b, a]) \
                * L.weight_sum_bits(m_a, T_a)
            exp_hist_bits += int(self.hist_wire_hist[b, a]) \
                * L.histogram_cell_bits(m_a, T_a)
            exp_vote_bits += int(self.hist_wire_votes[b, a]) \
                * L.vote_entry_bits(cls, m_a, T_a) if vote_pp else 0
            if stuck:
                exp_q += pl_last * int(self.hist_p[b, a])
        assert led.bits_coresets == got_core * L.example_bits(n), (
            led.bits_coresets, got_core)
        assert led.bits_weight_sums == exp_ws_bits, (
            led.bits_weight_sums, exp_ws_bits)
        assert led.bits_histograms == exp_hist_bits, (
            led.bits_histograms, exp_hist_bits)
        assert led.bits_votes == exp_vote_bits, (
            led.bits_votes, exp_vote_bits)
        assert int(self.wire_q_points[b]) == exp_q, (
            int(self.wire_q_points[b]), exp_q)
        assert int(self.wire_q_counts[b]) == exp_q
        return {
            "bits_coresets": led.bits_coresets,
            "coreset_examples_gathered": got_core,
            "bits_weight_sums": led.bits_weight_sums,
            "weight_sum_scalars_gathered": got_ws,
            "bits_histograms": led.bits_histograms,
            "histogram_scalars_merged": int(
                self.hist_wire_hist[b, :n_att].sum()),
            "bits_votes": led.bits_votes,
            "vote_proposals_exchanged": int(
                self.hist_wire_votes[b, :n_att].sum()),
            "quarantine_msgs": int(self.wire_q_points[b]),
            "collective_bytes": int(self.wire_bytes[b]),
        }


def finalize_sharded(state: dict, x, y, alive0, cfg: BoostConfig, cls,
                     m_true=None, mesh: Mesh | None = None,
                     ) -> ShardedClassifyResult:
    """Materialise a host result from stepped sharded state.

    Same inputs as ``batched.finalize`` plus the state dict's wire
    counters.  Returns a ``ShardedClassifyResult``: every
    ``BatchedClassifyResult`` field (same shapes/dtypes — hypotheses
    [B, t_buf, P] float32, [B] int32 counters, [B, k, mloc] bool
    masks) plus the measured collective payloads ([B, A] int32
    ``hist_wire_*``, [B] int32 ``wire_*``) that
    ``validate_ledger`` checks against the Theorem 4.1 accounting
    (docs/ledger.md).  Pure materialisation, no protocol math."""
    with obs_trace.span("finalize.state", "engine", engine="sharded"):
        out = jax.device_get(state)
    with obs_trace.span("finalize.inputs", "engine", engine="sharded"):
        x, y, alive0 = np.asarray(x), np.asarray(y), np.asarray(alive0)
    return ShardedClassifyResult(
        hypotheses=out["h_params"], rounds=out["rounds"],
        ok=np.asarray(out["done"]), attempts=out["attempt"],
        alive=out["alive"], disputed=out["disputed"],
        min_loss=out["min_loss"],
        hist_stuck=out["hist_stuck"], hist_rounds=out["hist_rounds"],
        hist_alive=out["hist_alive"], hist_p=out["hist_p"],
        x=x, y=y, alive0=alive0, cfg=cfg, cls=cls,
        m_true=None if m_true is None else np.asarray(m_true),
        hist_players=out["hist_players"],
        hist_players_h=out["hist_players_h"],
        hist_players_last=out["hist_players_last"],
        hist_wire_core=out["hist_wire_core"],
        hist_wire_ws=out["hist_wire_ws"],
        hist_wire_hist=out["hist_wire_hist"],
        hist_wire_votes=out["hist_wire_votes"],
        wire_bytes=out["wire_bytes"],
        wire_q_points=out["wire_q_points"],
        wire_q_counts=out["wire_q_counts"],
        mesh_devices=1 if mesh is None else mesh.shape[AXIS])


def run_accurately_classify_sharded(x, y, keys, cfg: BoostConfig, cls,
                                    mesh: Mesh | None = None, alive=None,
                                    no_center: bool = False,
                                    compiled=None, m_true=None,
                                    player_sched=None,
                                    ) -> ShardedClassifyResult:
    """B-task AccuratelyClassify over a real ``players`` device mesh.

    Same contract as ``batched.run_accurately_classify_batched`` (and
    bit-identical outputs on identical inputs and schedules); ``mesh``
    defaults to ``make_players_mesh(k)`` over the host's devices.
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    B, k, mloc = x.shape[0], x.shape[1], x.shape[2]
    if k != cfg.k:
        raise ValueError(f"x has {k} players but cfg.k={cfg.k}")
    keys = jnp.asarray(keys)
    if keys.ndim == 0:
        keys = jax.random.split(keys, B)
    if keys.shape[0] != B:
        raise ValueError(f"need {B} task keys, got shape {keys.shape}")
    if alive is None:
        alive = jnp.ones((B, k, mloc), bool)
    else:
        alive = jnp.asarray(alive)
    sched = batched.canon_player_sched(player_sched, B, k)
    if mesh is None:
        mesh = make_players_mesh(k)
    if compiled is not None:
        out = compiled(x, y, alive, keys, sched)
    else:
        t_buf = cfg.num_rounds(k * mloc)
        fn = _build_sharded(mesh, cfg, cls, t_buf, no_center)
        out = fn(x, y, alive, keys, sched)
    return finalize_sharded(out, x, y, alive, cfg, cls, m_true=m_true,
                            mesh=mesh)
