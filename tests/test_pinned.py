"""Tie-pinned extrema (``repro.core.pinned``) and the Gumbel-max draw
that reads one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import approximation, weights
from repro.core.pinned import pinned_argmax, pinned_argmin


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.int8, np.bool_])
@pytest.mark.parametrize("axis", [0, 1, -1])
def test_pinned_extrema_take_the_first_of_equal_values(dtype, axis):
    rng = np.random.default_rng(1)
    v = rng.integers(0, 3, (6, 7, 5)).astype(dtype)   # many ties
    for fn, ref in ((pinned_argmax, np.argmax), (pinned_argmin, np.argmin)):
        got = np.asarray(jax.jit(fn, static_argnums=1)(jnp.asarray(v), axis))
        np.testing.assert_array_equal(got, ref(v, axis=axis))
        assert got.dtype == np.int32


def test_pinned_extrema_at_the_ends_of_the_float_line():
    v = jnp.asarray([[-np.inf, -np.inf, -np.inf],
                     [np.inf, 1.0, np.inf],
                     [-np.inf, 2.0, -np.inf]], jnp.float32)
    assert pinned_argmax(v).tolist() == [0, 0, 1]
    assert pinned_argmin(v).tolist() == [0, 1, 0]


@pytest.mark.parametrize("mloc", [64, 256, 4096])
def test_sampled_coreset_draws_a_live_row_as_drawn_op_by_op(mloc):
    """Compiled, the Gumbel-max draw picks the row it picks op by op,
    and always a live one.  XLA:CPU once evaluated the Gumbel noise
    differently in the max and in the equality test that followed it:
    at 256 rows with some dead, 147 of 160 draws matched no row and
    came back as row 256."""
    rng = np.random.default_rng(0)
    draw = jax.jit(approximation.sampled_coreset, static_argnums=3)
    for s in range(10):
        alive = jnp.asarray(rng.random(mloc) > 0.06)
        hits = jnp.asarray(rng.integers(0, 3, mloc).astype(np.int32))
        key = jax.random.key(s)
        got = np.asarray(draw(key, hits, alive, 16))
        logp = weights.normalized_log_probs(hits, alive) * weights.LN2
        g = jax.random.gumbel(key, (16, mloc), logp.dtype)
        np.testing.assert_array_equal(
            got, np.argmax(np.asarray(g) + np.asarray(logp)[None], axis=-1))
        assert np.asarray(alive)[got].all()
