"""Weighted per-node feature histograms (tree split finding).

For features X [c, F], per-node routed weights W [N, c] and signed
weights WY = W·y [N, c], computes

    hist_w [n, f, q] = Σ_i W[n, i] · 1[bin(X[i, f]) == q]
    hist_wy[n, f, q] = Σ_i WY[n, i] · 1[bin(X[i, f]) == q]

with ``bin(x) = clip(floor(x·Q), 0, Q−1)`` over the fixed [0, 1) grid
(the convention defined in ref.py) — the LightGBM-style histogram a
greedy tree grower reduces to best (feature, bin) splits per node.

The one-hot bin-membership tile never hits HBM: each grid step builds a
[BF·Qp, BC] compare tile in VMEM and contracts it at once on the MXU as
ONE 2-D matmul, ``[W; WY] [2N, BC] · onehotᵀ`` → [2N, BF·Qp] (the
A·Bᵀ form Mosaic lowers natively; a scatter would be row-serial).

Layout (what the TPU compiler requires of every block — the last two
block dims divisible by (8, 128) or equal to the array's):

* X is transposed to [B, F, c] so the point axis is the lane axis; its
  block is (BF, BC) with BC a multiple of 128 and BF the whole padded F
  (or, for very wide F, a multiple of 8 — see :func:`feature_block`).
* The bin axis is padded to Qp (a multiple of 8) so the compare tile
  [BF, Qp, BC] collapses to [BF·Qp, BC] without a relayout; F is padded
  so that BF·Qp is a multiple of 128 (a lane-aligned output tile).
* W and WY are stacked into one [B, 2N, c] operand, block (2N, BC).
* Leading (task, feature-block) block dims are squeezed (``None``).

Grid: (B, F/BF, c/BC), c innermost, the [2N, BF·Qp] output block
accumulated across the c steps (revisited blocks — the standard Pallas
reduction pattern).  The dot runs at HIGHEST precision: the f32
weights pass through exactly (the protocol snaps them to a dyadic grid,
so every histogram cell is an exact f32 sum — see
core/boost_attempt._center_erm).  VMEM per step, double-buffered
inputs and output included, is :func:`vmem_bytes`: ≈ 3.7 MiB at
N = 4, F = 28, Q = 64 (BC = 256), under a quarter of the 16 MiB of
scoped VMEM a v5e kernel may use by default.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.histogram.ref import bin_index

BC = 256                       # points per grid step (lane axis)
ONEHOT_BUDGET = 2 * 2 ** 20    # bytes of one [BF·Qp, BC] f32 compare tile


def padded_bins(bins: int) -> int:
    """Qp: the bin axis rounded up to a sublane multiple (8)."""
    return -(-bins // 8) * 8


def feature_block(F: int, bins: int) -> tuple[int, int]:
    """(BF, F_pad): features per grid step and the padded feature count.

    BF·Qp is a multiple of 128 (lane-aligned output tile).  One block
    holds every feature while its compare tile fits ONEHOT_BUDGET;
    wider F is split into blocks that are also sublane multiples (8)."""
    qp = padded_bins(bins)
    unit = 128 // math.gcd(qp, 128)
    if -(-F // unit) * unit * qp * BC * 4 <= ONEHOT_BUDGET:
        bf = -(-F // unit) * unit
        return bf, bf
    step = unit * 8 // math.gcd(unit, 8)
    bf = max(step, ONEHOT_BUDGET // (qp * BC * 4) // step * step)
    return bf, -(-F // bf) * bf


def vmem_bytes(n_nodes: int, F: int, bins: int) -> int:
    """VMEM one grid step holds: double-buffered X tile, weight tile and
    output block, plus the int32 iota and the f32 one-hot tile."""
    bf, _ = feature_block(F, bins)
    tile = bf * padded_bins(bins) * BC * 4
    return (2 * bf * BC * 4 + 2 * 2 * n_nodes * BC * 4 + 2 * tile
            + 2 * 2 * n_nodes * bf * padded_bins(bins) * 4)


def _hist_kernel(bins, qp, xt_ref, lhs_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = bin_index(xt_ref[...], bins)                       # [BF, BC]
    bf, bc = b.shape
    q = jax.lax.broadcasted_iota(jnp.int32, (bf, qp, bc), 1)
    onehot = (b[:, None, :] == q).astype(jnp.float32).reshape(bf * qp, bc)
    out_ref[...] += jax.lax.dot_general(
        lhs_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                # [2N, BF·Qp]


@functools.partial(jax.jit, static_argnames=("bins", "interpret"))
def hist_batched_pallas(x, w, wy, *, bins: int, interpret: bool = False):
    """x [B, c, F] f32; w, wy [B, N, c] f32 → (hist_w, hist_wy)
    [B, N, F, bins] f32.  One launch for one tree level of all B tasks;
    any c, F, bins (padding rows carry zero weight, padding features
    and bins are sliced off)."""
    B, c, F = x.shape
    N = w.shape[1]
    qp = padded_bins(bins)
    bf, fp = feature_block(F, bins)
    cp = -(-c // BC) * BC
    xt = jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, fp - F), (0, cp - c)))
    lhs = jnp.pad(jnp.concatenate([w, wy], axis=1),
                  ((0, 0), (0, 0), (0, cp - c)))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bins, qp),
        grid=(B, fp // bf, cp // BC),
        in_specs=[
            pl.BlockSpec((None, bf, BC), lambda b, f, ci: (b, f, ci)),
            pl.BlockSpec((None, 2 * N, BC), lambda b, f, ci: (b, 0, ci)),
        ],
        out_specs=pl.BlockSpec((None, None, 2 * N, bf * qp),
                               lambda b, f, ci: (b, f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, fp // bf, 2 * N, bf * qp),
                                       jnp.float32),
        interpret=interpret,
    )(xt, lhs)
    out = out.reshape(B, fp // bf, 2 * N, bf, qp).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, 2 * N, fp, qp)[:, :, :F, :bins]
    return out[:, :N], out[:, N:]


def hist_pallas(x, w, wy, *, bins: int, interpret: bool = False):
    """x [c, F]; w, wy [N, c] → (hist_w, hist_wy) [N, F, bins]: the
    single-task form, one task of :func:`hist_batched_pallas`."""
    hw, hwy = hist_batched_pallas(x[None], w[None], wy[None], bins=bins,
                                  interpret=interpret)
    return hw[0], hwy[0]
