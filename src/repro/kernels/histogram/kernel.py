"""Weighted per-node feature histograms (tree split finding).

For features X [c, F], per-node routed weights W [N, c] and signed
weights WY = W·y [N, c], computes

    hist_w [n, f, q] = Σ_i W[n, i] · 1[bin(X[i, f]) == q]
    hist_wy[n, f, q] = Σ_i WY[n, i] · 1[bin(X[i, f]) == q]

with ``bin(x) = clip(floor(x·Q), 0, Q−1)`` over the fixed [0, 1) grid
(the convention defined in ref.py) — the LightGBM-style histogram a
greedy tree grower reduces to best (feature, bin) splits per node.

The one-hot bin-membership tile never hits HBM: each grid step builds a
[BF·Qp, BC] bfloat16 compare tile in VMEM (0 and 1 are exact in
bfloat16) and contracts it at once on the MXU as ONE 2-D matmul,
``pieces [3·2N, BC] · onehotᵀ`` → [3·2N, BF·Qp] (the A·Bᵀ form Mosaic
lowers natively; a scatter would be row-serial).

Layout (what the TPU compiler requires of every block — the last two
block dims divisible by (8, 128) or equal to the array's):

* X is transposed to [B, F, c] so the point axis is the lane axis; its
  block is (BF, BC) with BC a multiple of 128 and BF the whole padded F
  (or, for very wide F, a multiple of 8 — see :func:`feature_block`).
* The bin axis is padded to Qp (a multiple of 8) so the compare tile
  [BF, Qp, BC] collapses to [BF·Qp, BC] (without a relayout where Qp
  is a multiple of 16, bfloat16's sublane tile); F is padded so that
  BF·Qp is a multiple of 128 (a lane-aligned output tile).
* W and WY are stacked into one [B, 2N, c] operand, block (2N, BC).
* Leading (task, feature-block) block dims are squeezed (``None``).

Grid: (B, F/BF, c/BC), c innermost, the [2N, BF·Qp] output block
accumulated across the c steps (revisited blocks — the standard Pallas
reduction pattern).

Exactness in one bfloat16 pass.  The body splits the float32 weight
block [W; WY] into three bfloat16 pieces with ``hi + mid + lo == w``
exactly (:func:`split_bf16x3`: 3 · 8 significant bits hold a float32's
24), stacks them as the [3·2N, BC] LHS, and contracts once at DEFAULT
precision with float32 accumulation; the three row groups of the
product are added into the output block.  Each product is a piece
times 0 or 1, so exact.  The protocol snaps every ERM weight to the
2^-23 grid (``weights.erm_weights``) and a party's weights sum to at
most its mixture share, so every partial sum of every piece, and of
the three, is an exact float32 number: the histograms are the exact
sums whatever the order, bit for bit what a float32 matmul gives (see
core/boost_attempt._center_erm).  Off such a grid the sums round in
float32 accumulation, as a float32 matmul's would.  The MXU streams
the tile once; a HIGHEST-precision float32 dot would split it into
bfloat16 pieces on the VPU and stream each.

The tile is counted at its element size (``ONEHOT_BYTES``) against
``ONEHOT_BUDGET``, which sets BF: 16 features a block at Q = 256.
VMEM per step, double-buffered inputs and output included, is
:func:`vmem_bytes`: ≈ 1.1 MiB at N = 4, F = 28, Q = 64 and ≈ 2.4 MiB
at N = 2, F = 2000, Q = 256 (BC = 256), under a sixth of the 16 MiB of
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
ONEHOT_BUDGET = 2 * 2 ** 20    # bytes of one [BF·Qp, BC] compare tile
ONEHOT_BYTES = 2               # its element: bfloat16, exact for 0 and 1


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
    bf = -(-F // unit) * unit
    if bf * qp * BC * ONEHOT_BYTES <= ONEHOT_BUDGET:
        return bf, bf
    step = unit * 8 // math.gcd(unit, 8)
    cap = ONEHOT_BUDGET // (qp * BC * ONEHOT_BYTES)
    bf = max(step, cap // step * step)
    return bf, -(-F // bf) * bf


def vmem_bytes(n_nodes: int, F: int, bins: int) -> int:
    """VMEM one grid step holds: double-buffered X tile, weight tile and
    output block, plus the [Qp, BC] int32 iota and the bf16 one-hot
    tile."""
    bf, _ = feature_block(F, bins)
    qp = padded_bins(bins)
    return (2 * bf * BC * 4 + 2 * 2 * n_nodes * BC * 4 + qp * BC * 4
            + bf * qp * BC * ONEHOT_BYTES + 2 * 2 * n_nodes * bf * qp * 4)


def split_bf16x3(w):
    """(hi, mid, lo) bfloat16 with ``hi + mid + lo == w`` exactly, for
    any finite float32 ``w`` whose pieces are not subnormal (none on the
    protocol's 2^-23 weight grid): each piece keeps the top 8
    significant bits of what the pieces before it left (the float32
    word's low 16 bits masked off: truncated, never rounded), and
    3 · 8 bits hold the 24 of a float32 significand."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type(bits & -65536, jnp.float32)
    hi = top(w)
    rest = w - hi
    mid = top(rest)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


def _hist_kernel(bins, qp, xt_ref, lhs_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = bin_index(xt_ref[...], bins)                       # [BF, BC]
    bf, bc = b.shape
    q = jax.lax.broadcasted_iota(jnp.int32, (qp, bc), 0)   # [Qp, BC]
    onehot = (b[:, None, :] == q).astype(jnp.bfloat16).reshape(bf * qp, bc)
    rows = lhs_ref.shape[0]                                # 2N
    pieces = jnp.concatenate(split_bf16x3(lhs_ref[...]))   # [3·2N, BC]
    part = jax.lax.dot_general(
        pieces, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)                # [3·2N, BF·Qp]
    out_ref[...] += (part[:rows] + part[rows:2 * rows]
                     + part[2 * rows:])


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
