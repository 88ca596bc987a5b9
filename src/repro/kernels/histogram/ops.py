"""Public wrapper: dispatch (ref or Pallas), reduce to splits.

Both entry points accept an optional leading batch (task) axis:
``x [c, F]`` is one task of the kernel's grid; ``x [B, c, F]`` lowers
to one launch whose outermost grid axis is the task — one tree level of
the center ERM of all B tasks (kernel.py pads to its blocks).

Routing policy (mirrors how the stump kernel is deployed): the Pallas
program is the TPU fast path; on CPU the pure-jnp ref IS the production
implementation (XLA:CPU lowers the one-hot einsum well, while
interpret-mode Pallas is a debugging tool, not a fast path — see
TESTING.md for forcing it).  :func:`node_histograms` therefore
dispatches ref-vs-Pallas on the backend unless ``interpret=True``
explicitly requests the interpreted kernel (the parity tests do).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.histogram import kernel as K
from repro.kernels.histogram.ref import (  # noqa: F401  (re-export oracle)
    best_splits_per_feature, best_splits_ref, bin_index,
    node_histograms_chunked_ref, node_histograms_ref, split_err_surface)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_histograms(x, w, wy, bins: int, interpret: bool):
    if x.ndim == 3:
        return K.hist_batched_pallas(x, w, wy, bins=bins,
                                     interpret=interpret)
    return K.hist_pallas(x, w, wy, bins=bins, interpret=interpret)


def _chunked_histograms(x, w, wy, bins: int, interpret: bool | None,
                        chunk_size: int):
    """Scan the dispatched kernel over point tiles (streaming tier).

    Whatever :func:`node_histograms` would run monolithically — jnp ref
    or (interpreted) Pallas — runs per ``chunk_size`` tile inside a
    ``lax.scan`` that folds into the [(B,) N, F, Q] accumulator, so the
    O(c·F·Q) intermediate never exceeds one tile.  Bitwise equal to the
    monolithic path on dyadic weights (exact f32 partial sums)."""
    c, F = x.shape[-2], x.shape[-1]
    pc = (-c) % chunk_size
    lead = ((0, 0),) if x.ndim == 3 else ()
    xp = jnp.pad(x, lead + ((0, pc), (0, 0)))   # pad rows: zero weight
    wp = jnp.pad(w, lead + ((0, 0), (0, pc)))   # ⇒ no-op in every bin
    wyp = jnp.pad(wy, lead + ((0, 0), (0, pc)))
    t = (c + pc) // chunk_size
    if x.ndim == 3:
        b, n = w.shape[0], w.shape[1]
        xt = jnp.moveaxis(xp.reshape(b, t, chunk_size, F), 1, 0)
        wt = jnp.moveaxis(wp.reshape(b, n, t, chunk_size), 2, 0)
        wyt = jnp.moveaxis(wyp.reshape(b, n, t, chunk_size), 2, 0)
        shape = (b, n, F, bins)
    else:
        n = w.shape[0]
        xt = xp.reshape(t, chunk_size, F)
        wt = jnp.moveaxis(wp.reshape(n, t, chunk_size), 1, 0)
        wyt = jnp.moveaxis(wyp.reshape(n, t, chunk_size), 1, 0)
        shape = (n, F, bins)

    def fold(acc, tile):
        hw, hwy = node_histograms(*tile, bins, interpret=interpret)
        return (acc[0] + hw, acc[1] + hwy), None

    init = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    (hw, hwy), _ = jax.lax.scan(fold, init, (xt, wt, wyt))
    return hw, hwy


def node_histograms(x, w, wy, bins: int, interpret: bool | None = None,
                    chunk_size: int | None = None):
    """(hist_w, hist_wy) [(B,) N, F, Q] — see ref.node_histograms_ref.

    ``interpret=None`` (default): Pallas on TPU, jnp ref elsewhere.
    ``interpret=True``: force the interpreted Pallas kernel (parity
    testing).  ``interpret=False``: force the compiled kernel.
    ``chunk_size``: accumulate over point tiles of that many examples
    (the streaming tier — caps the one-hot intermediate at one tile;
    bitwise-equal on the protocol's dyadic weights).  ``None`` is the
    monolithic path, unchanged.
    """
    if chunk_size is not None and chunk_size < x.shape[-2]:
        return _chunked_histograms(x, w, wy, bins, interpret, chunk_size)
    if interpret is None:
        if not _on_tpu():
            return node_histograms_ref(x, w, wy, bins)
        interpret = False
    return _pallas_histograms(x, w, wy, bins, interpret)


def best_node_splits(x, w, wy, bins: int, interpret: bool | None = None,
                     chunk_size: int | None = None):
    """Histogram + reduce: the best (feature, bin) split per node.

    Returns (feat, q, err) each [(B,) N] — the full split-finding step
    of one tree level in one call (kernel contraction + jnp reduction).
    ``chunk_size`` threads through to :func:`node_histograms`.
    """
    hw, hwy = node_histograms(x, w, wy, bins, interpret=interpret,
                              chunk_size=chunk_size)
    return best_splits_ref(hw, hwy)
