"""Depth-d axis-aligned decision trees grown by weighted histograms.

The protocol is agnostic to the hypothesis class — players ship
coresets, the center ships back ANY weighted-ERM hypothesis and the
wire pays ``hypothesis_bits`` per round (Theorem 4.1's bits scale with
the hypothesis description length, never with m).  Every class the repo
had so far is single-feature, so each scenario was axis-separable; this
class opens the multi-feature regime (XOR / checkerboard / bands —
concepts stumps provably cannot fit) with the LightGBM-style fast path:
per-node weighted feature histograms (``kernels/histogram``) reduced to
best (feature, bin) splits, level by level.

**Fixed-shape, array-encoded.**  A depth-d tree is a complete binary
tree: ``nodes = 2^d − 1`` internal nodes in level order, ``leaves =
2^d``.  Hypothesis encoding — a flat float32 vector (rides the
``erm/erm_batch/ensemble_predict`` contract and the engines' ensemble
buffers unchanged, like the 4-wide classes):

    params = [type=5 | feat_0..feat_{NI−1} | qbin_0..qbin_{NI−1}
              | sign_0..sign_{NL−1}]           (param_dim = 1+2·NI+NL)

Node j at level l (0-indexed flat id ``2^l − 1 + i``) routes a point
right iff ``bin(x[feat_j]) ≥ qbin_j`` where ``bin`` is the fixed
[0, 1)-grid map of kernels/histogram/ref.py — predict evaluates the
SAME comparison the grower optimised, so they can never disagree.  A
``qbin = 0`` split is degenerate (everything right): how an
unsplittable node (empty, pure, or tie) pads out the fixed shape.

**Greedy, not exact.**  Unlike the closed-form 1-D classes, tree ERM is
greedy level-wise split finding — the standard histogram-boosting trade
(exact depth-d ERM is NP-hard).  The stuck certificate is therefore
approximate: a stuck round means GREEDY found no 1/100-good tree.
Quarantine soundness is unaffected (disputed points get the pointwise-
optimal majority vote regardless of why the attempt stuck); only the
communication bound inherits the greedy slack.  Scenario note: greedy
needs the planted boundaries OFF-centre (a perfectly symmetric XOR has
a zero-gain root and greedy degenerates) — core/scenarios.py plants
asymmetric cuts for exactly this reason.

ERM weights follow the repo contract: w ≥ 0 sums to ~1 (mixture/c), a
zero-weight row contributes to no histogram, and an all-zero-weight
call degenerates to loss 0 with the deterministic first-candidate tree.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.histogram import ops as H

TYPE_TREE = 5.0


def _select(idx, values):
    """``values[idx]`` for ``idx`` in [0, len(values)), as a chain of
    selects over the static ids: no row indexes a table, so the device
    runs no per-row gather."""
    out = values[0]
    for i in range(1, len(values)):
        out = jnp.where(idx == i, values[i], out)
    return out


def descend(b, node, feat, qbin):
    """One level of the level-order descent.

    b [..., F] int32 bin ids; node [...] int32, each row's node among
    the level's N; feat, qbin [N] the level's splits → the child ids
    node·2 + 1[b[feat[node]] ≥ qbin[node]] in [0, 2N).  Each node's
    test reads its feature by an iota compare over the feature axis
    (exact in int32) and broadcasts only the node's scalars; the row's
    own test is then selected among the N — nothing gathers per row.
    """
    fid = jnp.arange(b.shape[-1], dtype=jnp.int32)
    bits = [(jnp.sum(jnp.where(fid == feat[i], b, jnp.int32(0)), axis=-1,
                     dtype=jnp.int32) >= qbin[i]).astype(jnp.int32)
            for i in range(feat.shape[0])]
    return node * 2 + _select(node, bits)


def route(b, feat, qbin):
    """b [..., F] int32 bin ids, feat/qbin [2^L − 1] the first L levels
    of a tree in level order → each row's node on level L, [...] int32
    in [0, 2^L): the leaf when L is the tree's depth."""
    node = jnp.zeros(b.shape[:-1], jnp.int32)
    for level in range((feat.shape[0] + 1).bit_length() - 1):
        lo, hi = (1 << level) - 1, (2 << level) - 1
        node = descend(b, node, feat[lo:hi], qbin[lo:hi])
    return node


@dataclasses.dataclass(frozen=True)
class HistogramTrees:
    """H = depth-``depth`` axis trees over [0,1)^F on a ``bins``-bin
    grid.  Hashable (a jit static / scheduler CompatKey component)."""

    num_features: int
    depth: int = 2
    bins: int = 32               # power of two: q/Q thresholds are exact

    # How split finding crosses the wire (core/boost_attempt._center_erm
    # dispatches on it; ledger.py charges it; scheduler.CompatKey hashes
    # it so mixed-mode traffic partitions into separate compile buckets):
    #   "coreset"   — players ship coresets, the center grows on pooled
    #                 examples (the paper's step 2(a) exchange);
    #   "histogram" — players ship per-node weighted histograms, the
    #                 merge is the sum — examples cross the wire only on
    #                 a stuck round (quarantine needs the points);
    #   "voting"    — LightGBM-style parallel voting: players ship top-k
    #                 per-node split proposals, a deterministic election
    #                 picks ≤ 2·topk candidate features, and one merged-
    #                 histogram round runs on the elected columns only.
    comm_mode: str = "coreset"
    vote_topk: int = 2           # proposals per node per player (voting)

    # Streaming tier (docs/streaming.md): when set, every histogram
    # build accumulates over point tiles of this many examples instead
    # of one monolithic [c, F, Q] one-hot — bitwise-equal on the
    # protocol's dyadic weights, hashable like every other field here.
    chunk_size: int | None = None

    # capability protocol (core/tasks.py, serve/scheduler): this class
    # consumes feature rows [.., F] and needs the randomized coreset
    needs_features: bool = dataclasses.field(default=True, init=False,
                                             repr=False)

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"depth must be ≥ 1, got {self.depth}")
        if self.bins < 2 or self.bins & (self.bins - 1):
            raise ValueError(
                f"bins must be a power of two ≥ 2, got {self.bins}")
        if self.comm_mode not in ("coreset", "histogram", "voting"):
            raise ValueError(
                f"comm_mode must be coreset|histogram|voting, "
                f"got {self.comm_mode!r}")
        if self.vote_topk < 1:
            raise ValueError(f"vote_topk must be ≥ 1, got {self.vote_topk}")

    # -- shape/bit accounting ---------------------------------------------

    @property
    def feature_dim(self) -> int:
        return self.num_features

    @property
    def nodes(self) -> int:
        return (1 << self.depth) - 1

    @property
    def leaves(self) -> int:
        return 1 << self.depth

    @property
    def param_dim(self) -> int:
        return 1 + 2 * self.nodes + self.leaves

    @property
    def elected(self) -> int:
        """Candidate features the voting election keeps per node —
        LightGBM's 2·topk cap (every elected feature was in SOME
        player's top-k, so ≤ min(F, k·topk), and 2·topk suffices for
        the majority-vote guarantee)."""
        return min(self.num_features, 2 * self.vote_topk)

    @property
    def bin_bits(self) -> int:
        return int(math.log2(self.bins))

    @property
    def feat_bits(self) -> int:
        return max(1, math.ceil(math.log2(max(self.num_features, 2))))

    @property
    def value_bits(self) -> int:
        """A grid point is F bin ids — what a coreset example costs on
        the wire (ledger.domain_size reads this)."""
        return self.num_features * self.bin_bits

    @property
    def vc_dim(self) -> int:
        """log2|H| = hypothesis_bits bounds the VC dimension of this
        finite class (|H| ≤ (F·Q)^nodes · 2^leaves)."""
        return self.hypothesis_bits()

    def hypothesis_bits(self) -> int:
        """nodes·(⌈log2 F⌉ + bin_bits) + leaves — each internal node
        names a feature and a bin edge, each leaf a sign."""
        return (self.nodes * (self.feat_bits + self.bin_bits)
                + self.leaves)

    # -- prediction --------------------------------------------------------

    def _unpack(self, p: jax.Array):
        ni = self.nodes
        feat = p[1:1 + ni].astype(jnp.int32)
        qbin = p[1 + ni:1 + 2 * ni].astype(jnp.int32)
        sign = p[1 + 2 * ni:1 + 2 * ni + self.leaves]
        return feat, qbin, sign

    def _predict_one(self, p: jax.Array, x: jax.Array) -> jax.Array:
        feat, qbin, sign = self._unpack(p)
        leaf = route(H.bin_index(x, self.bins), feat, qbin)
        s = _select(leaf, [sign[i] for i in range(self.leaves)])
        return jnp.where(s > 0, jnp.int8(1), jnp.int8(-1))

    def predict(self, params: jax.Array, x: jax.Array) -> jax.Array:
        """params [..., P], x [*pts, F] → int8 ±1 [*param_batch, *pts]."""
        params = jnp.asarray(params)
        if params.ndim == 1:
            return self._predict_one(params, x)
        flat = params.reshape((-1, params.shape[-1]))
        out = jax.vmap(lambda p: self._predict_one(p, x))(flat)
        return out.reshape(params.shape[:-1] + x.shape[:-1])

    # -- the weak learner --------------------------------------------------

    def erm(self, xs: jax.Array, ys: jax.Array, w: jax.Array):
        """Greedy level-wise histogram tree on (xs [c, F], ys, w).

        One ``node_histograms`` launch per level (2^l nodes fold into
        the kernel's node axis; under the engines' task-vmap the whole
        level of all B tasks is one batched contraction).  Returns
        (params [param_dim], loss) with loss = the returned tree's
        weighted error — closed-form from the leaf sums, same float
        values every engine computes (bitwise parity relies on it).
        """
        c = xs.shape[0]
        wy = w * ys.astype(w.dtype)
        b = H.bin_index(xs, self.bins)
        node = jnp.zeros((c,), jnp.int32)
        feats, qbins = [], []
        for level in range(self.depth):
            N = 1 << level
            onnode = (node[:, None]
                      == jnp.arange(N, dtype=jnp.int32)[None])    # [c, N]
            wn = jnp.where(onnode, w[:, None], 0.0).T             # [N, c]
            wyn = jnp.where(onnode, wy[:, None], 0.0).T
            f_n, q_n, _ = H.best_node_splits(xs, wn, wyn, self.bins,
                                             chunk_size=self.chunk_size)
            feats.append(f_n)
            qbins.append(q_n)
            node = descend(b, node, f_n, q_n)
        NL = self.leaves
        onleaf = (node[:, None] == jnp.arange(NL, dtype=jnp.int32)[None])
        w_leaf = jnp.sum(jnp.where(onleaf, w[:, None], 0.0), axis=0)
        wy_leaf = jnp.sum(jnp.where(onleaf, wy[:, None], 0.0), axis=0)
        sign = jnp.where(wy_leaf >= 0, 1.0, -1.0)    # sign(0) := +1
        loss = jnp.sum(0.5 * (w_leaf - jnp.abs(wy_leaf)))
        params = jnp.concatenate(
            [jnp.array([TYPE_TREE], jnp.float32),
             jnp.concatenate(feats).astype(jnp.float32),
             jnp.concatenate(qbins).astype(jnp.float32),
             sign.astype(jnp.float32)])
        return params, loss

    def erm_players(self, cx: jax.Array, cy: jax.Array, pw: jax.Array,
                    *, all_gather=None, interpret=None):
        """Distributed greedy grower — the ``comm_mode`` collectives.

        cx [kp, c, F] float32 / cy [kp, c] int8 ±1: per-player coreset
        shards; pw [kp] float32: per-player per-example weight
        (mixture/c — a dead player carries pw = 0 and contributes zero
        to every histogram and no votes).  With ``chunk_size`` set,
        each player's local histograms accumulate over point tiles —
        bitwise-equal to the monolithic build on the protocol's dyadic
        weights, so the parity contract below is chunking-invariant
        (docs/streaming.md).
        ``all_gather`` pools a [kp, …] per-player array to [k, …] in
        player order (identity when the caller already holds all k
        players — the host and batched engines; the sharded engine
        passes a real ``lax.all_gather``+reshape).  Returns (params
        [param_dim], loss), same encoding as :meth:`erm`.

        Per level, each player builds its local per-node histograms with
        the kernels/histogram triple (kp is the kernel's native batch
        axis); then either

        * **histogram**: gather + sum over the player axis — the merged
          global histogram, reduced to best splits exactly as the
          pooled-coreset grower would (``jnp.sum`` over the gathered
          [k, …] array, NOT a ``psum``: reduction order must not depend
          on mesh topology or bit-parity across engines breaks);
        * **voting**: each player proposes its ``vote_topk`` best
          features per node (stable argsort of per-feature best errors
          ⇒ lowest feature wins local ties); the election counts votes
          of players with pw > 0 and ranks features by
          ``votes·F + (F−1−f)`` — all ranks distinct, so ``lax.top_k``
          is fully deterministic: most votes wins, lowest feature
          breaks vote ties.  One merged-histogram round then runs on
          the ``elected`` columns only.

        Leaves come from the LAST level's merged histograms (prefix
        sums at the chosen split), so no extra payload is needed.  Each
        mode's float path is engine-independent (the parity tests pin
        host ≡ batched ≡ sharded per mode) but the per-player-partial
        summation order differs from the pooled grower's, so modes may
        disagree with each other in the last float bit — by design.
        """
        kp, c = cx.shape[0], cx.shape[1]
        F = self.num_features
        ag = all_gather if all_gather is not None else (lambda a: a)
        w = jnp.broadcast_to(pw[:, None], (kp, c))            # [kp, c]
        wy = w * cy.astype(w.dtype)
        b = H.bin_index(cx, self.bins)                        # [kp, c, F]
        node = jnp.zeros((kp, c), jnp.int32)
        feats, qbins = [], []
        sel = q_n = hw_m = hwy_m = None
        for level in range(self.depth):
            N = 1 << level
            onnode = (node[..., None]
                      == jnp.arange(N, dtype=jnp.int32))      # [kp, c, N]
            wn = jnp.where(onnode, w[..., None], 0.0)
            wyn = jnp.where(onnode, wy[..., None], 0.0)
            hw, hwy = H.node_histograms(
                cx, wn.transpose(0, 2, 1), wyn.transpose(0, 2, 1),
                self.bins, interpret=interpret,
                chunk_size=self.chunk_size)                   # [kp,N,F,Q]
            if self.comm_mode == "voting":
                _, err_f = H.best_splits_per_feature(hw, hwy)  # [kp,N,F]
                prop = jnp.argsort(err_f, axis=-1,
                                   stable=True)[..., :self.vote_topk]
                votes_all = ag(prop)                          # [k,N,topk]
                alive_all = ag(pw > 0)                        # [k]
                onefeat = ((votes_all[..., None]
                            == jnp.arange(F, dtype=jnp.int32))
                           & alive_all[:, None, None, None])
                votes = jnp.sum(onefeat.astype(jnp.int32),
                                axis=(0, 2))                  # [N, F]
                rank = votes * F + jnp.arange(F - 1, -1, -1,
                                              dtype=jnp.int32)
                _, elect = jax.lax.top_k(rank, self.elected)  # [N, E]
                gidx = elect[None, :, :, None]
                hw_e = jnp.take_along_axis(hw, gidx, axis=2)
                hwy_e = jnp.take_along_axis(hwy, gidx, axis=2)
                with jax.named_scope("hist_merge"):
                    hw_m = jnp.sum(ag(hw_e), axis=0)          # [N, E, Q]
                    hwy_m = jnp.sum(ag(hwy_e), axis=0)
                with jax.named_scope("split_search"):
                    sel, q_n, _ = H.best_splits_ref(hw_m, hwy_m)
                f_n = jnp.take_along_axis(elect, sel[:, None],
                                          axis=1)[:, 0]
            else:                                             # histogram
                with jax.named_scope("hist_merge"):
                    hw_m = jnp.sum(ag(hw), axis=0)            # [N, F, Q]
                    hwy_m = jnp.sum(ag(hwy), axis=0)
                with jax.named_scope("split_search"):
                    f_n, q_n, _ = H.best_splits_ref(hw_m, hwy_m)
                sel = f_n
            feats.append(f_n)
            qbins.append(q_n)
            node = descend(b, node, f_n, q_n)
        # -- leaves from the last level's merged histograms: the chosen
        # column's prefix sums at q give each child's (w, wy) exactly —
        # children interleave as [left_0, right_0, left_1, …], matching
        # the node·2 + (bin ≥ q) descent above.
        with jax.named_scope("split_search"):
            hw_sel = jnp.take_along_axis(
                hw_m, sel[:, None, None], axis=1)[:, 0]       # [N, Q]
            hwy_sel = jnp.take_along_axis(hwy_m, sel[:, None, None],
                                          axis=1)[:, 0]
            cw = jnp.cumsum(hw_sel, axis=-1)
            cwy = jnp.cumsum(hwy_sel, axis=-1)
            left_w = jnp.take_along_axis(cw - hw_sel, q_n[:, None],
                                         axis=-1)[:, 0]
            left_wy = jnp.take_along_axis(cwy - hwy_sel, q_n[:, None],
                                          axis=-1)[:, 0]
            w_leaf = jnp.stack([left_w, cw[:, -1] - left_w],
                               axis=-1).reshape(-1)
            wy_leaf = jnp.stack([left_wy, cwy[:, -1] - left_wy],
                                axis=-1).reshape(-1)
        sign = jnp.where(wy_leaf >= 0, 1.0, -1.0)    # sign(0) := +1
        loss = jnp.sum(0.5 * (w_leaf - jnp.abs(wy_leaf)))
        params = jnp.concatenate(
            [jnp.array([TYPE_TREE], jnp.float32),
             jnp.concatenate(feats).astype(jnp.float32),
             jnp.concatenate(qbins).astype(jnp.float32),
             sign.astype(jnp.float32)])
        return params, loss

    # -- task-generation capability (core/tasks.py) ------------------------

    def sample_points(self, rng: np.random.Generator, m: int):
        """m grid-snapped uniform points of [0, 1)^F (bin centres, so
        every q/Q threshold separates them exactly)."""
        u = rng.random((m, self.num_features))
        return ((np.floor(u * self.bins) + 0.5)
                / self.bins).astype(np.float32)

    def sample_target(self, rng: np.random.Generator, x: np.ndarray):
        """A random tree of this class: uniform node features, interior
        bin cuts and leaf signs (both label classes forced non-empty
        when possible, so targets aren't trivially constant)."""
        feat = rng.integers(0, self.num_features, size=self.nodes)
        qbin = rng.integers(1, self.bins, size=self.nodes)
        sign = rng.choice([-1.0, 1.0], size=self.leaves)
        if np.all(sign == sign[0]):
            sign[rng.integers(self.leaves)] = -sign[0]
        return np.concatenate(
            [[TYPE_TREE], feat, qbin, sign]).astype(np.float32)

    def pack_params(self, feat, qbin, sign) -> np.ndarray:
        """Host-side encoder for planted trees (core/scenarios.py)."""
        feat = np.asarray(feat).reshape(self.nodes)
        qbin = np.asarray(qbin).reshape(self.nodes)
        sign = np.asarray(sign).reshape(self.leaves)
        return np.concatenate(
            [[TYPE_TREE], feat, qbin, sign]).astype(np.float32)
