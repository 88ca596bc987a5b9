"""Per-kernel shape/dtype sweeps, interpret=True, against ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import weights
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.histogram import kernel as hist_kernel
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.histogram.ref import best_splits_ref, node_histograms_ref
from repro.kernels.mw_update import ops as mw_ops
from repro.kernels.mw_update.ref import mw_update_ref
from repro.kernels.stump import ops as stump_ops
from repro.kernels.stump.ref import stump_errors_ref


@pytest.mark.parametrize("m", [64, 1000, 8192, 16384])
@pytest.mark.parametrize("seed", [0, 1])
def test_mw_update_sweep(m, seed):
    rng = np.random.default_rng(seed)
    hits = jnp.asarray(rng.integers(0, 60, m), jnp.int32)
    correct = jnp.asarray(rng.random(m) < 0.5)
    alive = jnp.asarray(rng.random(m) < 0.85)
    new_hits, wsum = mw_ops.mw_update(hits, correct, alive)
    ref_hits = hits + jnp.where(correct & alive, 1, 0)
    ref_w = jnp.sum(jnp.where(alive,
                              jnp.exp2(-ref_hits.astype(jnp.float32)), 0.0))
    np.testing.assert_array_equal(np.asarray(new_hits),
                                  np.asarray(ref_hits))
    np.testing.assert_allclose(float(wsum), float(ref_w), rtol=1e-5)


def test_mw_update_block_partials():
    m, block = 512, 128
    rng = np.random.default_rng(2)
    hits = jnp.asarray(rng.integers(0, 20, m), jnp.int32)
    correct = jnp.asarray(rng.random(m) < 0.5)
    alive = jnp.ones(m, bool)
    from repro.kernels.mw_update import kernel as K
    nh, parts = K.mw_update_pallas(hits, correct, alive,
                                   interpret=True, block=block)
    rh, rp = mw_update_ref(hits, correct, alive, block)
    np.testing.assert_array_equal(np.asarray(nh), np.asarray(rh))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(rp),
                               rtol=1e-6)


@pytest.mark.parametrize("c,F,Q", [(32, 1, 8), (128, 8, 128),
                                   (257, 9, 130), (512, 16, 256)])
def test_stump_sweep(c, F, Q):
    rng = np.random.default_rng(c + F + Q)
    x = jnp.asarray(rng.standard_normal((c, F)) * 10, jnp.float32)
    w = rng.random(c).astype(np.float32)
    w = jnp.asarray(w / w.sum())
    y = jnp.asarray(rng.choice([-1.0, 1.0], c), jnp.float32)
    th = jnp.asarray(np.sort(rng.standard_normal((F, Q)) * 10, axis=1),
                     jnp.float32)
    got = stump_ops.stump_errors(x, w, y, th)
    ref = stump_errors_ref(x, w, y, th)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)
    assert got.shape == (F, Q, 2)


# Edge cases for the stump contraction: shapes straddling the block
# boundaries (±1 around BC/BF/BQ after caller padding), all-negative
# weights, duplicate thresholds — for both the 2-D and the batched
# (leading task axis) grids.
@pytest.mark.parametrize("c,F,Q", [(127, 7, 127), (129, 9, 129),
                                   (128, 8, 128), (1, 1, 1),
                                   (255, 17, 257)])
def test_stump_block_boundaries(c, F, Q):
    rng = np.random.default_rng(c * 31 + F * 7 + Q)
    x = jnp.asarray(rng.standard_normal((c, F)) * 5, jnp.float32)
    w = jnp.asarray(rng.random(c), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], c), jnp.float32)
    th = jnp.asarray(rng.standard_normal((F, Q)) * 5, jnp.float32)
    got = stump_ops.stump_errors(x, w, y, th, interpret=True)
    ref = stump_errors_ref(x, w, y, th)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)


def test_stump_all_negative_weights():
    """wy < 0 everywhere (every example labelled −1): the accumulated
    scores are all-negative, errors must still match the oracle."""
    rng = np.random.default_rng(0)
    c, F, Q = 130, 9, 127
    x = jnp.asarray(rng.standard_normal((c, F)), jnp.float32)
    w = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
    y = -jnp.ones((c,), jnp.float32)
    th = jnp.asarray(rng.standard_normal((F, Q)), jnp.float32)
    got = stump_ops.stump_errors(x, w, y, th, interpret=True)
    ref = stump_errors_ref(x, w, y, th)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)
    assert float(jnp.min(got)) >= -3e-5   # errors are non-negative


def test_stump_duplicate_thresholds():
    """Repeated θ values (ties with x values included) must produce
    identical columns — the ≥ comparison is exact, no fuzz."""
    rng = np.random.default_rng(1)
    c, F = 64, 4
    x = jnp.asarray(rng.integers(0, 8, (c, F)), jnp.float32)
    w = jnp.asarray(rng.random(c), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], c), jnp.float32)
    base = jnp.asarray(rng.integers(0, 8, (F, 1)), jnp.float32)
    th = jnp.tile(base, (1, 6))                    # 6 identical columns
    got = stump_ops.stump_errors(x, w, y, th, interpret=True)
    ref = stump_errors_ref(x, w, y, th)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)
    for q in range(1, 6):
        np.testing.assert_array_equal(np.asarray(got[:, q]),
                                      np.asarray(got[:, 0]))


@pytest.mark.parametrize("B,c,F,Q", [(1, 127, 7, 129), (3, 129, 9, 127),
                                     (2, 128, 8, 128), (4, 33, 3, 17)])
def test_stump_batched_sweep(B, c, F, Q):
    """The batched grid (leading task axis, per-task thresholds AND
    weights) against the batched oracle, at boundary shapes."""
    rng = np.random.default_rng(B * 97 + c + F + Q)
    x = jnp.asarray(rng.standard_normal((B, c, F)) * 5, jnp.float32)
    w = jnp.asarray(rng.random((B, c)), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], (B, c)), jnp.float32)
    th = jnp.asarray(rng.standard_normal((B, F, Q)) * 5, jnp.float32)
    got = stump_ops.stump_errors(x, w, y, th, interpret=True)
    ref = stump_errors_ref(x, w, y, th)
    assert got.shape == (B, F, Q, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)
    # each batch lane must equal its own unbatched launch
    for b in range(B):
        one = stump_ops.stump_errors(x[b], w[b], y[b], th[b],
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(one),
                                   rtol=3e-5, atol=3e-6)


def test_stump_batched_all_negative_and_duplicates():
    rng = np.random.default_rng(4)
    B, c, F, Q = 2, 129, 9, 130
    x = jnp.asarray(rng.integers(0, 6, (B, c, F)), jnp.float32)
    w = jnp.asarray(rng.random((B, c)) + 0.05, jnp.float32)
    y = -jnp.ones((B, c), jnp.float32)
    th = jnp.repeat(jnp.asarray(rng.integers(0, 6, (B, F, 1)),
                                jnp.float32), Q, axis=2)
    got = stump_ops.stump_errors(x, w, y, th, interpret=True)
    ref = stump_errors_ref(x, w, y, th)
    # Both sides form err = ½(W ∓ (2S − Σwy)), where W, S and Σwy are f32
    # sums of c terms bounded by Σ|w|.  Each such sum is off by at most
    # γ_c·Σ|w| (γ_c = c·u/(1 − c·u), u = 2^-24, any summation order), and
    # the three additions that combine operands of size ≤ 2Σ|w| add at
    # most 3u·2Σ|w| more, so one evaluation is within (2γ_c + 3u)·Σ|w| of
    # the exact error and two evaluations are within twice that.  With
    # y ≡ −1 the entries that should be 0 cancel operands of size ~2Σ|w|
    # (Σ|w| ≈ 70 here), so the bound is absolute, set by the task's
    # total weight — a fixed atol would claim more than f32 can give.
    u = 2.0 ** -24
    gamma = c * u / (1 - c * u)
    atol = 2 * (2 * gamma + 3 * u) * np.asarray(jnp.sum(jnp.abs(w), -1))
    diff = np.abs(np.asarray(got) - np.asarray(ref))
    np.testing.assert_array_less(
        diff, np.broadcast_to(atol[:, None, None, None], diff.shape))


# Histogram (tree split-finding) kernel: the parity bar is BITWISE.
# Inputs use dyadic-rational weights (multiples of 1/256), whose
# partial sums are all exactly representable in f32, so the sum is
# independent of accumulation order and kernel-vs-ref equality is
# assertable bit for bit — including on padded/ragged shapes where the
# kernel's block partition differs most from the ref einsum.
def _dyadic_hist_inputs(rng, c, F, N, bins):
    x = ((rng.integers(0, bins, (c, F)) + 0.5) / bins).astype(np.float32)
    w = (rng.integers(0, 256, (N, c)) / 256.0).astype(np.float32)
    wy = w * rng.choice([-1.0, 1.0], (N, c)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(wy)


@pytest.mark.parametrize("c,F,N,bins", [
    (128, 8, 1, 64), (130, 9, 3, 32), (1, 1, 1, 4),
    (257, 5, 4, 32), (127, 7, 2, 128),
])
def test_histogram_kernel_bitwise_parity(c, F, N, bins):
    rng = np.random.default_rng(c * 13 + F + N + bins)
    x, w, wy = _dyadic_hist_inputs(rng, c, F, N, bins)
    ref = node_histograms_ref(x, w, wy, bins)
    got = hist_ops.node_histograms(x, w, wy, bins, interpret=True)
    for g, r in zip(got, ref):
        assert g.shape == (N, F, bins)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("B,c,F,N,bins", [
    (1, 127, 7, 2, 32), (3, 129, 5, 4, 32), (2, 128, 8, 1, 64),
    (4, 33, 3, 2, 16),
])
def test_histogram_kernel_batched_bitwise_parity(B, c, F, N, bins):
    """The task-batched grid (outermost axis folds task × node) against
    the batched oracle AND each lane's own unbatched launch."""
    rng = np.random.default_rng(B * 97 + c + F + N)
    xs, ws, wys = zip(*[_dyadic_hist_inputs(rng, c, F, N, bins)
                        for _ in range(B)])
    x, w, wy = jnp.stack(xs), jnp.stack(ws), jnp.stack(wys)
    ref = node_histograms_ref(x, w, wy, bins)
    got = hist_ops.node_histograms(x, w, wy, bins, interpret=True)
    for g, r in zip(got, ref):
        assert g.shape == (B, N, F, bins)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    for b in range(B):
        one = hist_ops.node_histograms(x[b], w[b], wy[b], bins,
                                       interpret=True)
        for g, o in zip(got, one):
            np.testing.assert_array_equal(np.asarray(g[b]),
                                          np.asarray(o))


# The kernel contracts its bfloat16 one-hot tile in one MXU pass: each
# float32 weight enters as three bfloat16 pieces that sum back to it
# exactly, so every histogram cell stays the exact sum.
def _split_cases(kind, rng):
    n = 4096
    if kind == "positive":
        return rng.uniform(0.0, 1.0, n).astype(np.float32)
    if kind == "negative":
        return -rng.uniform(0.0, 1.0, n).astype(np.float32)
    if kind == "zero":
        return np.zeros(n, np.float32)
    if kind == "signed_wide_range":
        mag = rng.uniform(0.5, 1.0, n) * 2.0 ** rng.integers(-40, 40, n)
        return (mag * rng.choice([-1.0, 1.0], n)).astype(np.float32)
    # kind == "grid_24_bits": k · 2^-23 with k in [2^23, 2^24), k odd:
    # on the ERM weights' 2^-23 grid, all 24 significand bits set
    k = rng.integers(2 ** 22, 2 ** 23, n) * 2 + 1
    return (k * weights.ERM_WEIGHT_GRID * rng.choice([-1.0, 1.0], n)
            ).astype(np.float32)


@pytest.mark.parametrize("kind", ["positive", "negative", "zero",
                                  "signed_wide_range", "grid_24_bits"])
def test_bf16x3_split_puts_back_every_float32_bit(kind):
    w = _split_cases(kind, np.random.default_rng(len(kind)))
    hi, mid, lo = hist_kernel.split_bf16x3(jnp.asarray(w))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    pieces = [np.asarray(p, np.float32) for p in (hi, mid, lo)]
    np.testing.assert_array_equal(pieces[0] + pieces[1] + pieces[2], w)
    # each piece holds at most 8 significant bits of what was left: the
    # next piece is below the previous one's last bit
    for a, b in zip(pieces, pieces[1:]):
        assert np.all(np.abs(b) <= np.abs(a) * 2.0 ** -7)
    if kind == "grid_24_bits":
        # the last piece is 0 only where bits 9-16 of w are all 0
        assert np.mean(pieces[2] != 0) > 0.9


@pytest.mark.parametrize("k,c,F,N,bins", [
    (4, 3, 7, 2, 32), (3, 5, 5, 4, 16), (2, 8, 9, 1, 64), (16, 2, 3, 2, 8),
])
def test_histogram_kernel_bitwise_parity_on_erm_weights(k, c, F, N, bins):
    """The kernel's batch axis holds a task's k parties, each with c
    coreset rows at the ERM weight ``weights.erm_weights`` gives its
    random mixture share, routed to N nodes and signed by the label
    (``HistogramTrees.erm_players``).  Small coresets give weights with
    more than 16 significant bits, so the third bfloat16 piece is not
    zero: a kernel that dropped it would not pass."""
    rng = np.random.default_rng(k * 31 + c + F + N)
    lo_used = False
    for _ in range(3):
        mix = rng.dirichlet(np.ones(k)).astype(np.float32)
        pw = np.asarray(weights.erm_weights(jnp.asarray(mix), c))
        x = ((rng.integers(0, bins, (k, c, F)) + 0.5) / bins
             ).astype(np.float32)
        node = rng.integers(0, N, (k, c))
        w = np.where(node[:, None, :] == np.arange(N)[None, :, None],
                     pw[:, None, None], 0.0).astype(np.float32)
        wy = w * rng.choice(np.array([-1.0, 1.0], np.float32), (k, 1, c))
        lo_used |= np.any(np.asarray(hist_kernel.split_bf16x3(
            jnp.asarray(w))[2]) != 0)
        x, w, wy = jnp.asarray(x), jnp.asarray(w), jnp.asarray(wy)
        ref = node_histograms_ref(x, w, wy, bins)
        got = hist_ops.node_histograms(x, w, wy, bins, interpret=True)
        for g, r in zip(got, ref):
            assert g.shape == (k, N, F, bins)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    assert lo_used


def test_histogram_zero_weight_rows_and_out_of_range():
    """Zero-weight rows land nowhere; x outside [0, 1) clips to the
    edge bins (the same clip predict applies, so grower and predictor
    agree even on out-of-range points)."""
    bins = 16
    x = jnp.asarray([[-0.5], [0.0], [0.999], [1.5]], jnp.float32)
    w = jnp.asarray([[1.0, 0.0, 0.5, 0.25]], jnp.float32)
    wy = w
    hw, _ = hist_ops.node_histograms(x, w, wy, bins, interpret=True)
    assert float(hw[0, 0, 0]) == 1.0               # clipped low + w=0 row
    assert float(hw[0, 0, bins - 1]) == 0.75       # 0.999 and clipped 1.5


def test_best_splits_reduction():
    """best_splits_ref finds the provably optimal (feature, bin) on a
    hand-built histogram, ties to the first flat index."""
    hw = jnp.zeros((1, 2, 4), jnp.float32)
    hwy = jnp.zeros((1, 2, 4), jnp.float32)
    # feature 1: bins [+2, +2, -3, -3] → split at q=2 is perfect
    hw = hw.at[0, 1].set(jnp.asarray([2.0, 2.0, 3.0, 3.0]))
    hwy = hwy.at[0, 1].set(jnp.asarray([2.0, 2.0, -3.0, -3.0]))
    # feature 0: all weight in one bin, pure → any split scores 0 err
    hw = hw.at[0, 0, 1].set(10.0)
    hwy = hwy.at[0, 0, 1].set(10.0)
    f, q, err = best_splits_ref(hw, hwy)
    assert float(err[0]) == 0.0
    assert int(f[0]) == 0 and int(q[0]) == 0       # first flat tie


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 64, 4, 2, 32), (2, 128, 8, 8, 64), (1, 200, 4, 1, 16),
    (1, 256, 2, 2, 128),
])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, hd, window, dtype):
    rng = np.random.default_rng(S + H + window)
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
    got = flash_ops.flash_attention(q, k, v, causal=True, window=window)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
        window=window).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_attention_path():
    """models/attention full_attention(use_flash=True) == einsum path."""
    from repro.configs import base
    from repro.models import attention
    cfg = base.reduced(base.get_config("deepseek-7b"))
    key = jax.random.key(0)
    p = attention.init(key, cfg)
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model),
                          jnp.float32)
    pos = jnp.arange(64)[None]
    out_ein, _, _ = attention.full_attention(p, cfg, x, pos, causal=True)
    out_fl, _, _ = attention.full_attention(p, cfg, x, pos, causal=True,
                                            use_flash=True)
    np.testing.assert_allclose(np.asarray(out_ein, np.float32),
                               np.asarray(out_fl, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_vmem_budget_static():
    """BlockSpec working sets fit v5e VMEM (static check)."""
    from repro.kernels.flash_attention import kernel as FK
    from repro.kernels.histogram import kernel as HK
    from repro.kernels.mw_update import kernel as MK
    from repro.kernels.stump import kernel as SK
    vmem = 16 * 2 ** 20
    bq, bk, hd = FK.DEFAULT_BQ, FK.DEFAULT_BK, 256
    flash = (bq * hd + 2 * bk * hd + bq * bk + bq * hd + 2 * bq) * 4
    assert flash < vmem // 4
    assert MK.BLOCK * 4 * 4 < vmem // 4
    bc, bf, bqq = SK.BC, SK.BF, SK.BQ
    assert (bc * bf + bf * bqq + bc * bf * bqq) * 4 < vmem // 4
    # histogram: every level of a depth-≤4 tree at the deployment widths,
    # plus a wide-F (Epsilon-like) shape that splits into feature blocks
    for n_nodes in (1, 2, 4, 8):
        for F in (8, 28, 2000):
            for bins in (32, 64):
                assert HK.vmem_bytes(n_nodes, F, bins) < vmem // 3
