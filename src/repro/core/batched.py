"""Device-resident batched AccuratelyClassify engine, round-steppable.

The host-driven loop in :mod:`repro.core.classify` dispatches one
BoostAttempt at a time and round-trips to numpy for every quarantine —
``O(B · attempts)`` dispatches for B independent tasks.  This module
runs B tasks in ONE jitted program, and (since the fault-tolerance PR)
exposes the protocol as a **round-granular stepping API**:

* :func:`init_state`   — build the full protocol state (a pytree of
  arrays, msgpack-serializable for checkpoint/resume);
* :func:`run_rounds`   — advance every unfinished task by up to ``n``
  wire rounds (one step = one BoostAttempt round; attempt transitions —
  stuck→quarantine→retry, success, budget exhaustion — happen *inside*
  the step body, so a task crosses attempt boundaries mid-slice);
* :func:`finalize`     — materialise a :class:`BatchedClassifyResult`.

``run_rounds(state, ..., n=∞)`` is the whole protocol; running it in
slices (a preemptible scheduler, a checkpoint every N rounds) produces
bit-identical output to the uninterrupted run — the step body is the
same program either way, and the state round-trips exactly
(tests/test_fault_tolerance.py pins both).

**Fault tolerance.**  Every round consults a dynamic ``player_alive
[k]`` mask (row ``min(step, R−1)`` of a ``[R, k]`` schedule): an absent
player sends no coreset and no weight sum (its mixture weight is 0 and
its coreset rows are excluded from quarantine matching), receives no
hypothesis (its MW state freezes), and the ledger charges only bits
alive players actually moved (`ledger.boost_attempt_ledger_masked`).
With the default all-alive schedule every value — floats included — is
bit-identical to the pre-fault-tolerance engine; the host-reference
parity suite (tests/test_batched.py) keeps that honest.

Semantics are the reference loop's, bit for bit:

* the per-attempt PRNG stream is the same ``key, sub = split(key)``
  sequence ``run_accurately_classify`` performs on the host (keys are
  carried as raw ``key_data`` words so the state is pure numerics);
* the round bound is the paper's dynamic T = ⌈6·log2 m_alive⌉ per task
  per attempt, with m_alive counting examples of players alive at the
  attempt's first round;
* quarantine is the array form of np.unique/np.isin — masked
  point-matching against the stuck coreset (classify.match_points),
  with the dispute-table size from classify.distinct_count_masked so
  the communication ledger charges the identical bit counts.

Tasks finish at different attempt counts; finished lanes freeze (the
standard vmap-of-while masking) while stragglers continue.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import msgpack_ckpt
from repro.core import boost_attempt, classify, ledger as L, streaming, weak
from repro.core import weights as W
from repro.core.boost_attempt import step_scope
from repro.core.types import BoostConfig, ClassifyResult, Ledger
from repro.obs import trace as obs_trace


class StepState(NamedTuple):
    """Whole-protocol state of B tasks, one wire round at a time.

    Every field carries a leading ``[B]`` task axis; PRNG keys are raw
    ``key_data`` words (uint32) so the tuple is a plain-array pytree —
    msgpack-serializable via ckpt/msgpack_ckpt with no special cases.
    """

    # -- protocol-level ---------------------------------------------------
    attempt: jax.Array        # int32 — attempts executed so far
    done: jax.Array           # bool  — some attempt succeeded
    alive: jax.Array          # [k, mloc] current alive-example mask
    disputed: jax.Array       # [k, mloc] quarantined-example mask
    key_data: jax.Array       # task key (raw words)
    h_params: jax.Array       # [t_buf, P] winning ensemble, P=param_dim(cls)
    rounds: jax.Array         # int32 rounds of the winning attempt
    min_loss: jax.Array       # last center ERM loss (diagnostic)
    hist_stuck: jax.Array     # [A] bool   per-attempt stuck flag
    hist_rounds: jax.Array    # [A] int32  per-attempt rounds
    hist_alive: jax.Array     # [A] int32  alive examples entering attempt
    hist_p: jax.Array         # [A] int32  distinct disputed points
    hist_players: jax.Array       # [A] Σ_wire-rounds alive players
    hist_players_h: jax.Array     # [A] same over successful rounds only
    hist_players_last: jax.Array  # [A] alive players at the last round
    # -- in-attempt -------------------------------------------------------
    in_attempt: jax.Array     # bool — an attempt is in flight
    akey_data: jax.Array      # current attempt's round key (raw words)
    t: jax.Array              # int32 hypotheses produced this attempt
    bound: jax.Array          # int32 this attempt's round bound
    hits: jax.Array           # [k, mloc] MW state
    cur_h: jax.Array          # [t_buf, P] growing ensemble
    core_x: jax.Array         # [k, c(, F)] last round's pooled coreset
    core_y: jax.Array         # [k, c]
    step: jax.Array           # int32 global wire-round counter


# -- checkpoint identity of the stepping state ------------------------------
# Leaf names in a checkpoint are the StepState field names (stable
# across releases — renames are format breaks); fixed dtypes are
# validated on template-free restore.  core_x/core_y follow the task
# data's dtype (int32 shards or float32 feature rows) and restore at
# whatever dtype they were saved with.

STATE_TREEDEF = "repro.core.batched.StepState"

STATE_DTYPES = {
    "attempt": "int32", "done": "bool", "alive": "bool",
    "disputed": "bool", "key_data": "uint32", "h_params": "float32",
    "rounds": "int32", "min_loss": "float32", "hist_stuck": "bool",
    "hist_rounds": "int32", "hist_alive": "int32", "hist_p": "int32",
    "hist_players": "int32", "hist_players_h": "int32",
    "hist_players_last": "int32", "in_attempt": "bool",
    "akey_data": "uint32", "t": "int32", "bound": "int32",
    "hits": "int32", "cur_h": "float32", "step": "int32",
}


def check_state_dtypes(leaves: dict, dtypes: dict, what: str) -> None:
    """Fail loudly when a restored leaf's dtype drifted from the
    engine's declared layout (shared by both engines' reconstructors)."""
    for name, want in dtypes.items():
        got = np.dtype(np.asarray(leaves[name]).dtype)
        if got != np.dtype(want):
            raise ValueError(
                f"checkpoint leaf {name!r} of {what} has dtype {got} "
                f"but the engine expects {want} — refusing a silent "
                f"cast (bit-parity would break invisibly)")


def _unflatten_state(leaves: dict) -> StepState:
    missing = set(StepState._fields) - set(leaves)
    if missing:
        raise KeyError(f"checkpoint missing StepState leaves: "
                       f"{sorted(missing)}")
    check_state_dtypes(leaves, STATE_DTYPES, "batched.StepState")
    return StepState(**{f: leaves[f] for f in StepState._fields})


msgpack_ckpt.register_treedef(STATE_TREEDEF, _unflatten_state)


def num_rounds_dynamic(cfg: BoostConfig, m_alive: jax.Array) -> jax.Array:
    """Traced twin of ``BoostConfig.num_rounds`` (same f32 ops ⇒ same
    integer for every m, so the batched loop bound matches the host's)."""
    m = jnp.maximum(m_alive, 2).astype(jnp.float32)
    return jnp.ceil(cfg.rounds_factor * jnp.log2(m)).astype(jnp.int32)


def canon_player_sched(player_sched, B: int, k: int) -> jax.Array:
    """Normalise a player schedule to ``[B, R, k]`` bool.

    Accepts None (all alive, R = 1), ``[R, k]`` (shared by every task)
    or ``[B, R, k]``.  Row ``min(step, R−1)`` is the round's mask, so
    the final row extends forever.  Every round must keep ≥ 1 player
    alive (the mixture is undefined over zero senders).
    """
    if player_sched is None:
        return jnp.ones((B, 1, k), bool)
    sched = jnp.asarray(player_sched, bool)
    if sched.ndim == 2:
        sched = jnp.broadcast_to(sched[None], (B,) + sched.shape)
    if sched.shape[0] != B or sched.shape[2] != k:
        raise ValueError(
            f"player_sched {sched.shape} incompatible with B={B}, k={k}")
    if not bool(jnp.all(jnp.any(sched, axis=-1))):
        raise ValueError("player_sched has a round with zero alive "
                         "players — the protocol cannot proceed")
    return sched


def init_state(x, y, keys, cfg: BoostConfig, alive=None,
               t_buf: int | None = None, cls=None) -> StepState:
    """Fresh protocol state for a [B, k, mloc(, F)] batch.

    Inputs: ``x`` [B, k, mloc] int32 domain points (integer track) or
    [B, k, mloc, F] float32 feature rows; ``y`` [B, k, mloc] int8 ±1
    labels; ``keys`` [B] PRNG keys (one per task); ``alive`` optional
    [B, k, mloc] bool (False = padding rows, masked out of every
    coreset, weight sum and ledger charge); ``t_buf`` ensemble-buffer
    rounds (defaults to ``cfg.num_rounds(k·mloc)``).  ``cls`` sizes
    the ensemble buffers (``weak.param_dim`` — classes with wider
    hypothesis vectors than the 4-wide default, e.g. the histogram
    trees, need it); None keeps the legacy 4-wide layout.

    Returns a ``StepState`` — a plain pytree of device arrays (int32
    counters, bool masks, float32 ensemble/coreset buffers, uint32
    PRNG key data; no Python objects), so it round-trips through
    ``ckpt.msgpack_ckpt`` template-free.  Contract: ``init_state`` →
    ``run_rounds``* → ``finalize`` in ANY slicing is bit-identical to
    the single-dispatch engine run, which is itself bit-identical to
    the host reference loop given the same keys (docs/architecture.md;
    pinned in tests/test_batched.py, tests/test_fault_tolerance.py).
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    B, k, mloc = x.shape[0], x.shape[1], x.shape[2]
    p_dim = weak.param_dim(cls)
    if alive is None:
        alive = jnp.ones((B, k, mloc), bool)
    else:
        alive = jnp.asarray(alive)
    if t_buf is None:
        t_buf = cfg.num_rounds(k * mloc)
    a_max = cfg.opt_budget + 1
    c = cfg.coreset_size
    kd = jax.random.key_data(jnp.asarray(keys))
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    return StepState(
        attempt=i32((B,)), done=jnp.zeros((B,), bool),
        alive=alive, disputed=jnp.zeros_like(alive),
        key_data=kd,
        h_params=jnp.zeros((B, t_buf, p_dim), jnp.float32),
        rounds=i32((B,)), min_loss=jnp.zeros((B,), jnp.float32),
        hist_stuck=jnp.zeros((B, a_max), bool),
        hist_rounds=i32((B, a_max)), hist_alive=i32((B, a_max)),
        hist_p=i32((B, a_max)), hist_players=i32((B, a_max)),
        hist_players_h=i32((B, a_max)),
        hist_players_last=i32((B, a_max)),
        in_attempt=jnp.zeros((B,), bool),
        akey_data=jnp.zeros_like(kd),
        t=i32((B,)), bound=i32((B,)),
        hits=W.init_hits((B, k, mloc)),
        cur_h=jnp.zeros((B, t_buf, p_dim), jnp.float32),
        core_x=jnp.zeros((B, k, c) + x.shape[3:], x.dtype),
        core_y=jnp.zeros((B, k, c), y.dtype),
        step=i32((B,)))


def _one_step(cfg: BoostConfig, cls, x, y, x_orders, sched,
              s: StepState) -> StepState:
    """ONE wire round of ONE task (vmap-ed over the batch axis).

    LOCKSTEP: core/sharded_batched.py mirrors this body with
    device-shard state + collectives; keep them in sync — the exact
    parity tests (tests/test_sharded_batched.py) fail on divergence.
    """
    a_max = cfg.opt_budget + 1
    k = x.shape[0]
    with step_scope("lane_state"):
        active = (~s.done) & (s.attempt < a_max)
        pa = sched[jnp.minimum(s.step, sched.shape[0] - 1)]      # [k]
        # ---- attempt start (no-op when one is already in flight) ------
        start = ~s.in_attempt
        tkey = jax.random.wrap_key_data(s.key_data)
        nk, sub = jax.random.split(tkey)
        key_data = jnp.where(start, jax.random.key_data(nk), s.key_data)
        akey_data = jnp.where(start, jax.random.key_data(sub),
                              s.akey_data)
        m_alive = jnp.sum((s.alive & pa[:, None]).astype(jnp.int32))
        a = s.attempt
        bound = jnp.where(start, num_rounds_dynamic(cfg, m_alive),
                          s.bound)
        hits = jnp.where(start, W.init_hits(x.shape[:2]), s.hits)
        cur_h = jnp.where(start, jnp.zeros_like(s.cur_h), s.cur_h)
        t = jnp.where(start, 0, s.t)
        hist_alive = jnp.where(start, s.hist_alive.at[a].set(m_alive),
                               s.hist_alive)
    # ---- one BoostAttempt round (the reference round body) ------------
    with step_scope("sort_order"):
        y_sorted = jnp.take_along_axis(y, x_orders, axis=1)
        alive_sorted = jnp.take_along_axis(s.alive, x_orders, axis=1)
    carry = boost_attempt._Carry(
        t=t, it=jnp.int32(0), stuck=jnp.asarray(False),
        hits=hits, key=jax.random.wrap_key_data(akey_data),
        h_params=cur_h,
        core_idx=jnp.zeros((k, cfg.coreset_size), jnp.int32),
        core_x=s.core_x, core_y=s.core_y, min_loss=s.min_loss)
    out = boost_attempt._round_body(
        cfg, cls, x, y, s.alive, x_orders, y_sorted, alive_sorted,
        carry, player_alive=pa)
    with step_scope("lane_state"):
        stuck = out.stuck
        success = (~stuck) & (out.t >= bound)
        ended = stuck | success
        k_alive = jnp.sum(pa.astype(jnp.int32))
        nxt = StepState(
            attempt=jnp.where(ended, a + 1, a),
            done=s.done | success,
            alive=s.alive,
            disputed=s.disputed,
            key_data=key_data,
            h_params=jnp.where(success, out.h_params, s.h_params),
            rounds=jnp.where(success, out.t, s.rounds),
            min_loss=out.min_loss,
            hist_stuck=jnp.where(ended, s.hist_stuck.at[a].set(stuck),
                                 s.hist_stuck),
            hist_rounds=jnp.where(ended, s.hist_rounds.at[a].set(out.t),
                                  s.hist_rounds),
            hist_alive=hist_alive,
            hist_p=s.hist_p,
            hist_players=s.hist_players.at[a].add(k_alive),
            hist_players_h=s.hist_players_h.at[a].add(
                jnp.where(stuck, 0, k_alive)),
            hist_players_last=s.hist_players_last.at[a].set(k_alive),
            in_attempt=~ended,
            akey_data=jax.random.key_data(out.key),
            t=out.t,
            bound=bound,
            hits=out.hits,
            cur_h=out.h_params,
            core_x=out.core_x, core_y=out.core_y,
            step=s.step + 1)
        # finished lanes freeze (vmap-of-while masking)
        nxt = jax.tree_util.tree_map(
            lambda new, old: jnp.where(active, new, old), nxt, s)
    return nxt, Stuck(stuck & active, out.core_x, pa)


class Stuck(NamedTuple):
    """What a lane's quarantine needs from the round it stuck in."""
    stuck: jax.Array       # the round stuck (and the lane was active)
    core_x: jax.Array      # [k, c(, F)] the pooled coreset
    senders: jax.Array     # [k] the players who sent it


def quarantine(cfg: BoostConfig, x, alive, st: Stuck):
    """Full-point quarantine of one lane's stuck round, masked to the
    round's senders: (dead_new [k, mloc], P = distinct points disputed).

    LOCKSTEP with the sharded engine, which calls it on its shard.
    The step loops run it only on rounds where some lane stuck
    (``lax.cond`` over the whole batch): under ``vmap`` a per-lane cond
    would run on every round, and matching every point against the k·c
    coreset points — then counting the distinct ones — is per-point
    work no other round needs."""
    core_flat = st.core_x.reshape((-1,) + st.core_x.shape[2:])
    valid_flat = jnp.repeat(st.senders, cfg.coreset_size)
    masked_flat = classify.mask_invalid_points(core_flat, valid_flat)
    dead_new = alive & classify.match_points(x, masked_flat) & st.stuck
    p_count = jnp.where(
        st.stuck, classify.distinct_count_masked(core_flat, valid_flat), 0)
    return dead_new, p_count


def _quarantine_lane(cfg: BoostConfig, x, s: StepState,
                     st: Stuck) -> StepState:
    dead_new, p_count = quarantine(cfg, x, s.alive, st)
    return s._replace(
        alive=s.alive & ~dead_new, disputed=s.disputed | dead_new,
        hist_p=jnp.where(st.stuck, s.hist_p.at[s.attempt - 1].set(p_count),
                         s.hist_p))


def _run_steps(x, y, sched, state: StepState, n, cfg: BoostConfig,
               cls) -> StepState:
    """Advance every active task by up to ``n`` wire rounds (traced)."""
    a_max = cfg.opt_budget + 1
    x1d = x if x.ndim == 3 else x[..., 0]
    # hoisted per slice; chunk-local runs under cfg.chunk_size (bitwise
    # identical to the monolithic argsort — streaming tier)
    with step_scope("sort_order"):
        x_orders = jax.vmap(jax.vmap(lambda v: streaming.sort_order(
            v, cfg.chunk_size, cfg.domain_size)))(x1d)

    def active(s: StepState):
        return (~s.done) & (s.attempt < a_max)

    def cond(carry):
        s, i = carry
        with step_scope("lane_state"):
            return jnp.any(active(s)) & (i < n)

    def body(carry):
        s, i = carry
        s2, st = jax.vmap(functools.partial(_one_step, cfg, cls))(
            x, y, x_orders, sched, s)
        with step_scope("quarantine"):
            s2 = jax.lax.cond(
                jnp.any(st.stuck),
                lambda s2: jax.vmap(functools.partial(
                    _quarantine_lane, cfg))(x, s2, st),
                lambda s2: s2, s2)
        with step_scope("lane_state"):
            return s2, i + 1

    out, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return out


_RUN_FOREVER = jnp.int32(2 ** 30)


@functools.partial(jax.jit, static_argnames=("cfg", "cls"))
def _run_rounds_jit(x, y, sched, state, n, cfg, cls):
    return _run_steps(x, y, sched, state, n, cfg, cls)


def run_rounds(state: StepState, x, y, cfg: BoostConfig, cls,
               n: int | None = None, player_sched=None) -> StepState:
    """Advance the protocol by up to ``n`` wire rounds (None = to
    completion).

    ``state``: a ``StepState`` from :func:`init_state` (or a restored
    checkpoint of one); ``x``/``y``: the SAME [B, k, mloc(, F)] /
    [B, k, mloc] arrays the state was initialised with (data stays
    outside the state so checkpoints hold O(state), not O(m));
    ``player_sched``: optional [R, k] or [B, R, k] bool per-wire-round
    player-alive schedule (see :func:`canon_player_sched`).  Returns
    the advanced ``StepState``; tasks already done pass through
    unchanged.

    ``n`` is traced — every slice size shares one compiled program per
    input signature, so preempting at an arbitrary round never
    recompiles.  Bitwise contract: any slicing (1/3/7/… rounds per
    call) produces the same final state, bit for bit, as one
    ``n=None`` call (tests/test_fault_tolerance.py); with
    ``cfg.chunk_size`` set, the chunked sort path is bitwise identical
    to the monolithic argsort, so slicing AND chunking are both
    invisible in every output (docs/streaming.md,
    tests/test_streaming.py)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    B, k = x.shape[0], x.shape[1]
    sched = canon_player_sched(player_sched, B, k)
    n_arr = _RUN_FOREVER if n is None else jnp.int32(n)
    with obs_trace.span("run_rounds", "engine", engine="batched", B=B,
                        n=(-1 if n is None else int(n))):
        return _run_rounds_jit(x, y, sched, state, n_arr, cfg, cls)


@functools.partial(jax.jit, static_argnames=("cfg", "cls", "t_buf"))
def _classify_batched_jit(x, y, alive0, keys, sched, cfg, cls, t_buf):
    with step_scope("lane_state"):
        state = init_state(x, y, keys, cfg, alive=alive0, t_buf=t_buf,
                           cls=cls)
    return _run_steps(x, y, sched, state, _RUN_FOREVER, cfg, cls)


def stack_for_dispatch(items, B: int):
    """Stack admitted (x, y, alive, key) tuples into bucket arrays.

    ``items`` holds up to B tasks already padded to a common [k, mloc];
    short batches are filled by duplicating lane 0 (a live lane — dead
    filler would spin through the whole opt_budget and a batch is as
    slow as its slowest lane).  Returns (x, y, alive, keys, n_real);
    lanes ≥ n_real are filler and their results must be discarded.
    """
    n_real = len(items)
    if not 0 < n_real <= B:
        raise ValueError(f"need 1..{B} items, got {n_real}")
    items = list(items) + [items[0]] * (B - n_real)
    x = np.stack([it[0] for it in items])
    y = np.stack([it[1] for it in items])
    alive = np.stack([it[2] for it in items])
    key_data = np.stack([np.asarray(jax.random.key_data(it[3]))
                         for it in items])
    keys = jax.random.wrap_key_data(jnp.asarray(key_data))
    return x, y, alive, keys, n_real


def lower_classify(x, y, alive, keys, cfg: BoostConfig, cls,
                   player_sched=None):
    """AOT-compile the batched engine for one input signature.

    Returns a ``jax.stages.Compiled`` executable with the statics
    (cfg, cls, t_buf) baked in — call it as ``compiled(x, y, alive,
    keys, player_sched)`` on arrays of exactly this shape/dtype.
    Unlike the implicit jit cache, the caller owns the executable's
    lifetime: dropping it (e.g. a serving compile-cache eviction) really
    frees the program, and re-lowering really recompiles.  Output is
    bit-identical to the jit path (same trace, same compiler).
    """
    t_buf = cfg.num_rounds(x.shape[1] * x.shape[2])
    sched = canon_player_sched(player_sched, x.shape[0], x.shape[1])
    with obs_trace.span("compile", "compile", engine="batched",
                        B=int(x.shape[0]), mloc=int(x.shape[2])):
        return obs_trace.compile_published(
            _classify_batched_jit, jnp.asarray(x), jnp.asarray(y),
            jnp.asarray(alive), keys, sched, cfg, cls, t_buf)


@dataclasses.dataclass
class BatchedClassifyResult:
    """Host view of one batched dispatch (B tasks).

    ``ok[b]`` is False iff task b exhausted ``opt_budget`` attempts —
    the batched analogue of the reference loop's RuntimeError.  The
    dispute table of task b is reconstructible from ``disputed[b]``
    alone (full-point quarantine ⇒ counts are the initially-alive
    counts; see classify.dispute_table).
    """

    hypotheses: np.ndarray   # [B, T_buf, P], P = weak.param_dim(cls)
    rounds: np.ndarray       # [B]
    ok: np.ndarray           # [B] bool
    attempts: np.ndarray     # [B]
    alive: np.ndarray        # [B, k, mloc] final alive mask
    disputed: np.ndarray     # [B, k, mloc]
    min_loss: np.ndarray     # [B]
    hist_stuck: np.ndarray   # [B, A]
    hist_rounds: np.ndarray  # [B, A]
    hist_alive: np.ndarray   # [B, A]
    hist_p: np.ndarray       # [B, A]
    # inputs, kept for per-task reconstruction
    x: np.ndarray
    y: np.ndarray
    alive0: np.ndarray
    cfg: BoostConfig
    cls: object
    # optional [B] true sample sizes — when the serving layer pads a
    # request's shards up to a bucket mloc, the protocol's |S| is still
    # the request's own m, and the dispute-report bit width ⌈log2 m⌉
    # must charge that, not the padded capacity
    m_true: np.ndarray | None = None
    # per-attempt alive-player sums under the dropout mask ([B, A]); an
    # all-alive run carries wire_rounds·k / rounds·k / k and the ledger
    # reduces bit-for-bit to the unmasked accounting
    hist_players: np.ndarray | None = None
    hist_players_h: np.ndarray | None = None
    hist_players_last: np.ndarray | None = None

    @property
    def batch(self) -> int:
        return int(self.rounds.shape[0])

    def _attempt_players(self, b: int, a: int):
        """(player_rounds, player_h_rounds, players_last) of attempt a,
        falling back to the all-alive counts for legacy results."""
        if self.hist_players is None:
            wire = int(self.hist_rounds[b, a]) \
                + (1 if self.hist_stuck[b, a] else 0)
            return (wire * self.cfg.k,
                    int(self.hist_rounds[b, a]) * self.cfg.k, self.cfg.k)
        return (int(self.hist_players[b, a]),
                int(self.hist_players_h[b, a]),
                int(self.hist_players_last[b, a]))

    def ledger(self, b: int) -> Ledger:
        """Bit-identical to the Ledger the reference loop accumulates
        (all players alive); under a dropout mask, charges only bits
        alive players actually sent.  docs/ledger.md walks every
        charge; the sharded twin's ``validate_ledger`` cross-checks
        the same numbers against measured collective payloads."""
        cfg, cls = self.cfg, self.cls
        k, mloc = self.x.shape[1], self.x.shape[2]
        n = L.domain_size(cls)
        m_eff = (k * mloc if self.m_true is None
                 else int(self.m_true[b]))
        m_bits_m = max(int(np.ceil(np.log2(max(m_eff, 2)))), 1)
        led = Ledger()
        for a in range(int(self.attempts[b])):
            stuck = bool(self.hist_stuck[b, a])
            pl_rounds, pl_h, pl_last = self._attempt_players(b, a)
            led = led + L.boost_attempt_ledger_masked(
                cfg, cls, max(int(self.hist_alive[b, a]), 2),
                int(self.hist_rounds[b, a]), stuck,
                pl_rounds, pl_h, pl_last)
            if stuck:
                p = int(self.hist_p[b, a])
                led.bits_control += pl_last * p * L.point_bits(n)
                led.bits_dispute += pl_last * p * 2 * m_bits_m
        return led

    def per_task(self, b: int, player_mask=None) -> ClassifyResult:
        """Materialise task b as a reference-shaped ClassifyResult.

        ``player_mask`` ([k] bool) restricts the dispute-table label
        counts to the given players' copies — pass the surviving-player
        set of a fault scenario so the D-vote is pointwise-optimal over
        the shards that are still there.
        """
        if not self.ok[b]:
            raise RuntimeError(
                f"task {b} exceeded opt_budget={self.cfg.opt_budget}")
        alive0 = self.alive0[b]
        if player_mask is not None:
            alive0 = alive0 & np.asarray(player_mask, bool)[:, None]
        pts, pos, neg = classify.dispute_table(
            self.x[b], self.y[b], alive0, self.disputed[b])
        n_att = int(self.attempts[b])
        return ClassifyResult(
            hypotheses=jnp.asarray(self.hypotheses[b]),
            rounds=int(self.rounds[b]),
            dispute_x=jnp.asarray(pts),
            dispute_y=(jnp.asarray(pos), jnp.asarray(neg)),
            dispute_count=int(pts.shape[0]),
            attempts=n_att,
            stuck_history=[bool(s) for s in self.hist_stuck[b, :n_att]],
            ledger=self.ledger(b))

    def classifier(self, b: int,
                   player_mask=None) -> classify.ResilientClassifier:
        return classify.make_classifier(
            self.cls, self.per_task(b, player_mask=player_mask))


def finalize(state: StepState, x, y, alive0, cfg: BoostConfig, cls,
             m_true=None) -> BatchedClassifyResult:
    """Materialise a (host) result from stepped protocol state.

    ``state``: a completed (or mid-protocol) ``StepState``;
    ``x``/``y``/``alive0``: the dispatch inputs, kept on the result
    for per-task reconstruction (``per_task``/``classifier``);
    ``m_true``: optional [B] int true sample sizes — when the serving
    layer padded shards up to a bucket mloc, the ledger's dispute-bit
    width must charge the request's own ⌈log2 m⌉, not the padded
    capacity.  Returns a ``BatchedClassifyResult`` of host numpy
    arrays: ``hypotheses`` [B, t_buf, P] float32, ``rounds``/
    ``attempts`` [B] int32, ``ok`` [B] bool, ``alive``/``disputed``
    [B, k, mloc] bool, plus per-attempt histories [B, A].  Pure
    materialisation — no protocol math happens here, so finalizing a
    restored checkpoint equals finalizing the original state bit for
    bit (tests/test_preemption.py)."""
    with obs_trace.span("finalize.state", "engine", engine="batched"):
        out = jax.device_get(state)
    with obs_trace.span("finalize.inputs", "engine", engine="batched"):
        x, y, alive0 = np.asarray(x), np.asarray(y), np.asarray(alive0)
    return BatchedClassifyResult(
        hypotheses=out.h_params, rounds=out.rounds,
        ok=np.asarray(out.done), attempts=out.attempt,
        alive=out.alive, disputed=out.disputed, min_loss=out.min_loss,
        hist_stuck=out.hist_stuck, hist_rounds=out.hist_rounds,
        hist_alive=out.hist_alive, hist_p=out.hist_p,
        x=x, y=y, alive0=alive0, cfg=cfg, cls=cls,
        m_true=None if m_true is None else np.asarray(m_true),
        hist_players=out.hist_players,
        hist_players_h=out.hist_players_h,
        hist_players_last=out.hist_players_last)


def run_accurately_classify_batched(x, y, keys, cfg: BoostConfig, cls,
                                    alive=None, compiled=None,
                                    m_true=None, player_sched=None,
                                    ) -> BatchedClassifyResult:
    """B-task AccuratelyClassify in one device dispatch.

    x, y: [B, k, mloc] int shards or [B, k, mloc, F] feature rows;
    keys: [B] PRNG keys (one per task — the same key given to the
    reference loop reproduces it exactly) or a single key to split.
    ``alive``: optional [B, k, mloc] initial mask (False = padding, so
    ragged batches are padded to a common mloc and masked out).
    ``compiled``: optional executable from :func:`lower_classify` for
    this signature — the serving layer's compile cache passes it so a
    dispatch can never trigger an implicit recompile.
    ``m_true``: optional [B] true per-task sample sizes (see
    ``BatchedClassifyResult.m_true``).
    ``player_sched``: optional [R, k] or [B, R, k] per-round
    player-alive schedule (see :func:`canon_player_sched`) — the
    infrastructure-adversary hook (dropout/flaky/rejoin).
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    B, k, mloc = x.shape[0], x.shape[1], x.shape[2]
    keys = jnp.asarray(keys)
    if keys.ndim == 0:                       # one typed key → B streams
        keys = jax.random.split(keys, B)
    if keys.shape[0] != B:
        raise ValueError(f"need {B} task keys, got shape {keys.shape}")
    if alive is None:
        alive = jnp.ones((B, k, mloc), bool)
    else:
        alive = jnp.asarray(alive)
    sched = canon_player_sched(player_sched, B, k)
    if compiled is not None:
        out = compiled(x, y, alive, keys, sched)
    else:
        t_buf = cfg.num_rounds(k * mloc)
        out = _classify_batched_jit(x, y, alive, keys, sched, cfg, cls,
                                    t_buf)
    return finalize(out, x, y, alive, cfg, cls, m_true=m_true)
