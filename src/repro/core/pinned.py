"""Tie-pinned reductions — the repo-law replacements for bare argmin/argmax.

Backend tie-breaking of ``jnp.argmin``/``jnp.argmax``/``lax.top_k`` is
NOT a contract: XLA:CPU happens to return the first occurrence, but TPU
reduction layouts make no such promise, and the whole value proposition
of the engines (bit-identical host/batched/sharded outputs, engine-
independent ERM winners) collapses if a tie can resolve differently per
backend.  Every selection on a value surface that can tie — ERM
candidate errors, split gains, vote elections — must therefore go
through a helper that spells the tie-break out in portable ops.

These helpers pin ties to the LOWEST index along the reduced axis,
in ONE reduction over (value, index) pairs: the pair with the better
value wins, and of equal values the lower index (no argmin/argmax
primitive reaches the jaxpr — ``tools/repro_lint`` audits traced
engines for exactly that).  One reduction reads each value once.  An
extremum followed by an equality test reads the values twice, and
XLA:CPU evaluated a fused producer (the Gumbel noise of
``approximation.sampled_coreset``) differently in the two fusions: the
test then matched no entry, and the index fell off the end of the
axis.  On values the compiler evaluates alike the result is the bare
op's, so adopting them is invisible to the parity suites.

``kernels/histogram/ref._pinned_argmin`` is the extremum-and-test
construction, kept local so the kernel oracle stays dependency-free;
it reads histogram errors that are exact sums.  This module is the
canonical import for everything outside the kernel triples.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _bound(dtype, high: bool):
    """The dtype's largest (``high``) or smallest value: the identity
    of a max or min reduction."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if high else -jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.array(high, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if high else info.min, dtype)


def _pinned_extremum(v: jnp.ndarray, axis: int, larger: bool):
    """Index of the largest (``larger``) or smallest value of v along
    ``axis``, ties to the lowest index.  v holds no NaN: every caller
    reduces errors, gains, masks, log-weights or logits.  (Handling NaN
    in the pairwise pick made the tree cell's Gumbel-max draw 7 %
    slower on a v5e.)"""
    v = jnp.asarray(v)
    axis = axis % v.ndim
    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)

    def pick(a, b):
        # a total order on NaN-free values (better value, then lower
        # index), so the result does not depend on the reduction's order
        (av, ai), (bv, bi) = a, b
        first = ((av > bv) if larger else (av < bv)) | (
            (av == bv) & (ai < bi))
        return lax.select(first, av, bv), lax.select(first, ai, bi)

    init = (_bound(v.dtype, high=not larger), jnp.int32(v.shape[axis]))
    return lax.reduce((v, idx), init, pick, (axis,))[1]


def pinned_argmin(v: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Index of the minimum along ``axis``, ties pinned to the lowest
    index — explicitly, not via argmin's backend-dependent tie order."""
    return _pinned_extremum(v, axis, larger=False)


def pinned_argmax(v: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Index of the maximum along ``axis``, ties pinned to the lowest
    index (the mirror of :func:`pinned_argmin`)."""
    return _pinned_extremum(v, axis, larger=True)
