"""Multiplicative-weights state in log2 space.

The paper's update is ``W_{t+1}(z) = W_t(z) · 2^{-1[h_t(x)=y]}`` with
``W_1 ≡ 1``.  After ``T = ⌈6·log2 m⌉`` rounds a weight can be as small as
``2^{-T}``; storing the *hit count* ``H_t(z) = -log2 W_t(z)`` as an int32
is exact, overflow-free, and makes the paper's claim that the weight sums
``W_t^{(i)}`` need only ``O(log |S|)`` bits literal.

Dead (quarantined) examples are handled with an ``alive`` mask: they
contribute 0 to every distribution and are never sampled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN2 = 0.6931471805599453


def init_hits(shape) -> jax.Array:
    """H_1 ≡ 0  ⇔  W_1 ≡ 1."""
    return jnp.zeros(shape, dtype=jnp.int32)


def update_hits(hits: jax.Array, correct: jax.Array,
                alive: jax.Array) -> jax.Array:
    """W·2^{-1[h(x)=y]}  ⇔  H += 1[h(x)=y]; only alive examples move.
    Preserves the hits dtype (int16 suffices for T ≤ 32767 rounds and
    halves the protocol's dominant HBM term — §Perf P2)."""
    return hits + (correct & alive).astype(hits.dtype)


def log_weight_sum(hits: jax.Array, alive: jax.Array,
                   axis=None) -> jax.Array:
    """log2 of  Σ_{alive} 2^{-hits}, computed stably.

    This is the per-player ``W_t^{(i)}`` of step 2(b), in log2 space.
    Dead entries contribute -inf.
    """
    logw = jnp.where(alive, -hits.astype(jnp.float32), -jnp.inf)
    # log2-sum-exp2, stable under a per-axis max shift.
    mx = jnp.max(logw, axis=axis, keepdims=True)
    mx_safe = jnp.where(jnp.isfinite(mx), mx, 0.0)
    s = jnp.sum(jnp.exp2(logw - mx_safe), axis=axis, keepdims=True)
    out = mx_safe + jnp.log2(jnp.maximum(s, 1e-30))
    out = jnp.where(jnp.isfinite(mx), out, -jnp.inf)
    if axis is not None:
        out = jnp.squeeze(out, axis=axis)
    else:
        out = jnp.reshape(out, ())
    return out


def normalized_log_probs(hits: jax.Array, alive: jax.Array,
                         axis: int = -1) -> jax.Array:
    """log2 p_t(z) = -hits - log2 W  (−inf on dead entries)."""
    logw = jnp.where(alive, -hits.astype(jnp.float32), -jnp.inf)
    return logw - jnp.expand_dims(
        log_weight_sum(hits, alive, axis=axis), axis)


def probs(hits: jax.Array, alive: jax.Array, axis: int = -1) -> jax.Array:
    """The paper's p_t distribution (probability per example)."""
    return jnp.exp2(normalized_log_probs(hits, alive, axis=axis))


def mixture_weights(log_wsums: jax.Array) -> jax.Array:
    """W_t^{(i)} / W_t  from per-player log2 sums (step 2(c)).

    Players whose entire shard is dead get weight 0.
    """
    finite = jnp.isfinite(log_wsums)
    shifted = jnp.where(finite, log_wsums, -jnp.inf)
    mx = jnp.max(shifted)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    w = jnp.exp2(shifted - mx)
    return w / jnp.maximum(jnp.sum(w), 1e-30)


# Grid of the center ERM's per-example weights.  They total Σ mix = 1
# (plus at most k·c·2^-24 of rounding), so every weight sum the ERM
# forms — histogram cells, prefix sums, leaf sums — is a multiple of
# 2^-23 below 2, and every error term (half such a sum or difference)
# a multiple of 2^-24 below 1: all EXACT in f32.  Exact sums do not
# depend on reduction order, which XLA picks per program: unsnapped,
# the single-task host program and the vmapped batched program summed
# one tree histogram in different orders, a zero-error tie between two
# splits broke on the rounding residue, and the runs diverged.
# Snapping moves a weight by ≤ 2^-24 (a total variation ≤ k·c·2^-24,
# 5e-4 at k·c = 8192 — far inside the ε = 1/100 the coreset concedes).
ERM_WEIGHT_GRID = 2.0 ** -23


def erm_weights(mix: jax.Array, c: int) -> jax.Array:
    """Per-example ERM weight of each player's c coreset rows: its
    mixture weight / c, snapped to :data:`ERM_WEIGHT_GRID`."""
    return jnp.round(mix / c / ERM_WEIGHT_GRID) * ERM_WEIGHT_GRID
