# lint-fixture-path: src/repro/core/fixture_rl006.py
"""RL006 pass: spans wrap the dispatch on the host; named_scope inside."""
import functools

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace


def _round(carry):
    s, i = carry
    with jax.named_scope("round"):          # device-visible label: allowed
        return s + jnp.float32(1.0), i + 1


@functools.partial(jax.jit, static_argnames=())
def _run(s):
    out, _ = jax.lax.while_loop(lambda c: c[1] < 4, _round,
                                (s, jnp.int32(0)))
    return out


def run(s):
    """Host wrapper: the span OUTSIDE the traced closure."""
    with obs_trace.span("run_rounds", "engine", engine="fixture"):
        return _run(jnp.asarray(s, jnp.float32))
