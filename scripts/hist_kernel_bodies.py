"""Time the histogram kernel's body alone, and check it bit for bit.

    PYTHONPATH=src python3 scripts/hist_kernel_bodies.py [--units 16] \
        [--rows 1024] [--features 2000] [--bins 256] [--reps 10] [--seed 0]

Runs one launch of the kernel's grid, (units, F/BF, rows/BC), at each
tree level N = 1 and N = 2, with these bodies:

* ``highest_f32``: a float32 one-hot tile contracted at
  ``Precision.HIGHEST`` (the kernel before its one-pass form), BF = 8;
* ``build_floor``: the same float32 tile summed on the VPU, no matmul:
  what building the tile costs alone;
* ``bf16x3``: the kernel's own body (``kernel._hist_kernel``), at
  BF = 8 and at the BF its byte budget gives.

Prints one JSON line per (body, N): milliseconds a launch (median of
``--reps`` after a warm-up) and microseconds a grid step.  Then one line
per launch: whether each contracting body's histograms equal, bit for
bit, a float64 count on the host, and each other.  The weights are the
protocol's, ``weights.erm_weights`` of a random mixture over the units,
each unit's rows routed to one of the N nodes; a third launch (N = 2)
gives a few rows of each unit weights that fill all three of the
kernel's bfloat16 pieces.  Needs a TPU;
``--interpret`` runs the same at a small size on the CPU (its times
mean nothing).
"""

import argparse
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import weights
from repro.kernels.histogram import kernel as K
from repro.kernels.histogram.ref import bin_index


def _highest_f32(bins, qp, xt_ref, lhs_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = bin_index(xt_ref[...], bins)
    bf, bc = b.shape
    q = jax.lax.broadcasted_iota(jnp.int32, (bf, qp, bc), 1)
    onehot = (b[:, None, :] == q).astype(jnp.float32).reshape(bf * qp, bc)
    out_ref[...] += jax.lax.dot_general(
        lhs_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _build_floor(bins, qp, xt_ref, lhs_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = bin_index(xt_ref[...], bins)
    bf, bc = b.shape
    q = jax.lax.broadcasted_iota(jnp.int32, (bf, qp, bc), 1)
    onehot = (b[:, None, :] == q).astype(jnp.float32).reshape(bf * qp, bc)
    part = onehot.reshape(bf * qp // 8, 8, bc).sum(axis=0)     # [8, BC]
    out_ref[:, :bc] += part[:out_ref.shape[0]]


def launch(body, bf, bins, xt, lhs, interpret):
    """The kernel's pallas_call with ``body`` at feature block ``bf``:
    xt [B, Fp, cp], lhs [B, 2N, cp] → [B, 2N, Fp, Qp]."""
    B, fp, cp = xt.shape
    rows, qp = lhs.shape[1], K.padded_bins(bins)
    out = pl.pallas_call(
        functools.partial(body, bins, qp),
        grid=(B, fp // bf, cp // K.BC),
        in_specs=[
            pl.BlockSpec((None, bf, K.BC), lambda b, f, ci: (b, f, ci)),
            pl.BlockSpec((None, rows, K.BC), lambda b, f, ci: (b, 0, ci)),
        ],
        out_specs=pl.BlockSpec((None, None, rows, bf * qp),
                               lambda b, f, ci: (b, f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, fp // bf, rows, bf * qp),
                                       jnp.float32),
        interpret=interpret,
    )(xt, lhs)
    out = out.reshape(B, fp // bf, rows, bf, qp).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, rows, fp, qp)


def inputs(rng, units, rows, F, bins, N, fp, full_bits):
    """xt [units, fp, rows] on bin centres; lhs [units, 2N, rows]: each
    unit's ERM weight on the row's node, then signed by its label.
    ``full_bits``: in place of the ERM weights, 4 rows of each unit
    carry weights below 1/4 on the 2^-23 grid (up to 22 significant
    bits, so all three bfloat16 pieces), the other rows 0."""
    x = ((rng.integers(0, bins, (units, F, rows)) + 0.5) / bins)
    xt = np.zeros((units, fp, rows), np.float32)
    xt[:, :F] = x
    mix = rng.dirichlet(np.ones(units)).astype(np.float32)
    pw = np.asarray(weights.erm_weights(jnp.asarray(mix), rows))
    pw = np.broadcast_to(pw[:, None], (units, rows))
    if full_bits:
        pw = np.zeros((units, rows), np.float32)
        hot = rng.permuted(np.tile(np.arange(rows), (units, 1)), axis=1)[:, :4]
        np.put_along_axis(pw, hot, rng.integers(0, 2 ** 21, hot.shape)
                          * np.float32(2.0 ** -23), 1)
    node = rng.integers(0, N, (units, rows))
    w = np.where(node[:, None, :] == np.arange(N)[None, :, None],
                 pw[:, None, :], 0.0).astype(np.float32)
    y = rng.choice(np.array([-1.0, 1.0], np.float32), (units, 1, rows))
    return xt, np.concatenate([w, w * y], axis=1)


def exact(xt, lhs, F, bins):
    """[units, 2N, F, bins] float64 histograms, counted on the host."""
    b = np.clip(np.floor(xt[:, :F] * np.float32(bins)), 0, bins - 1)
    idx = (np.arange(F)[:, None] * bins + b.astype(np.int64))  # [u, F, c]
    out = np.zeros((lhs.shape[0], lhs.shape[1], F * bins))
    for u in range(lhs.shape[0]):
        for r in range(lhs.shape[1]):
            wr = np.broadcast_to(lhs[u, r][None, :], idx[u].shape)
            out[u, r] = np.bincount(idx[u].ravel(), wr.ravel(),
                                    minlength=F * bins)
    return out.reshape(lhs.shape[0], lhs.shape[1], F, bins)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--units", type=int, default=16)
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--features", type=int, default=2000)
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.interpret:
        print("needs a TPU (or --interpret)", file=sys.stderr)
        return 1
    F, bins = a.features, a.bins
    budget_bf, _ = K.feature_block(F, bins)
    bodies = [("highest_f32", _highest_f32, 8),
              ("build_floor", _build_floor, 8), ("bf16x3", K._hist_kernel, 8)]
    if budget_bf != 8:
        bodies.append(("bf16x3", K._hist_kernel, budget_bf))
    blk = max(bf for _, _, bf in bodies)
    fp = -(-F // blk) * blk
    rows = -(-a.rows // K.BC) * K.BC
    rng = np.random.default_rng(a.seed)
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    for N, full_bits in ((1, False), (2, False), (2, True)):
        xt, lhs = inputs(rng, a.units, rows, F, bins, N, fp, full_bits)
        xt_d, lhs_d = jnp.asarray(xt), jnp.asarray(lhs)
        got = {}
        for name, body, bf in bodies:
            fn = jax.jit(functools.partial(launch, body, bf, bins,
                                           interpret=a.interpret))
            out = fn(xt_d, lhs_d).block_until_ready()
            times = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                fn(xt_d, lhs_d).block_until_ready()
                times.append(time.perf_counter() - t0)
            steps = a.units * (fp // bf) * (rows // K.BC)
            ms = statistics.median(times) * 1e3
            print(json.dumps({"body": name, "N": N, "bf": bf,
                              "weights": "full_bits" if full_bits else "erm",
                              "grid_steps": steps, "ms_per_launch": ms,
                              "us_per_step": ms * 1e3 / steps,
                              "ms_min": min(times) * 1e3,
                              "ms_max": max(times) * 1e3,
                              "device": device}), flush=True)
            if name != "build_floor":
                got[f"{name}.bf{bf}"] = np.asarray(out)[:, :, :F, :bins]
        ref = exact(xt, lhs, F, bins).astype(np.float32)
        pieces = [np.count_nonzero(np.asarray(p, np.float32))
                  for p in K.split_bf16x3(jnp.asarray(lhs))]
        first = next(iter(got.values()))
        print(json.dumps({
            "N": N, "seed": a.seed,
            "weights": "full_bits" if full_bits else "erm",
            "nonzero_pieces_hi_mid_lo": pieces,
            "equal_to_exact": {k: bool(np.array_equal(v, ref))
                               for k, v in got.items()},
            "all_bodies_equal": all(np.array_equal(v, first)
                                    for v in got.values()),
            "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
