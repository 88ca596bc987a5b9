"""Run the boosting service's main path once on a TPU, and check it.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the players mesh only

One process, through the entry points a user calls — ``repro.launch.
serve``'s ``run_classify`` and ``run_serve_stream`` with arguments from
its own parser — and the engines they drive.  On one chip:

(a) threshold tenants at PRODUCTION_BOOST widths (k = 16 players,
    512-point coresets, domain 2^20, OPT budget 256): B = 32 tasks under
    targeted-heavy label noise, at m = 2^18 points each where the
    deployment has 2^20 (the cut is printed with its reason).  Every
    task must end ok with E_S(f) ≤ OPT.
(b) tree tenants: depth-2 trees over F = 28 features (Higgs-shaped) on
    Q = 32 bins, split finding by histogram merge, a planted XOR;
    B = 8 tasks of m = 2^17.  Every task must reach E_S(f) ≤ OPT, and
    the compiled engine must hold the Pallas histogram kernel.
(c) parity on the chip: two lanes each of (a) and (b) at a smaller m.
    The host loop, the batched engine and the sharded engine on a
    one-device mesh agree bit for bit, and the ledger matches the
    payloads the sharded engine measured.
(d) the continuous-batching server: 48 requests on a bursty trace, at
    the (a) widths, with one dispatch preempted, checkpointed under
    ``experiments/`` and resumed.

With ``--chips 4``: the sharded engine over a four-device players mesh
for the (a) and (b) deployments' widths at a small B and m (the cut is
printed), against the batched engine on the first chip alone —
bit-identical, ledger validated.

Each phase asserts what it needs; the first failure ends the run with
its traceback and a non-zero exit.  Earlier lines report each phase's
wall and compile seconds and the device's peak memory (chip readings of
this smoke run, not benchmark results).  The last line of standard
output is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "experiments", "chip_smoke")

PRODUCTION = ["--k", "16", "--coreset", "512", "--domain", str(1 << 20),
              "--opt-budget", "256", "--seed", "0"]
THRESHOLDS = ["--cls", "thresholds", "--scenario", "targeted_heavy"]
TREES = ["--cls", "tree", "--comm-mode", "histogram", "--features", "28",
         "--tree-depth", "2", "--tree-bins", "32", "--scenario", "xor"]
THRESHOLDS_B, THRESHOLDS_M = 32, 1 << 18
TREES_B, TREES_M = 8, 1 << 17
PARITY_M = 1 << 16
PARITY_LANES = 2
CUTS = [
    "(a) runs m = 2^18 where the deployment has 2^20: at 2^20 the phase "
    "alone ran past 1350 s on one v5e, over this script's 1200 s",
    "(c) runs m = 2^16: the host loop runs its lanes one at a time and "
    "each engine compiles its own program per shape",
]
# --chips 4 compares bit for bit at the deployments' widths; a second
# of four chips costs four, so B and m are small
FOUR_CHIPS = [(THRESHOLDS, "thresholds", 8, 1 << 16),
              (TREES, "trees", 4, 1 << 16)]
FOUR_CHIPS_CUT = ("--chips 4 runs B = 8 threshold and B = 4 tree tasks "
                  "of m = 2^16: every second there is charged four times")

_compile_s: collections.Counter = collections.Counter()


def _on_compile_event(event: str, secs: float, **_):
    if event.startswith("/jax/core/compile/"):
        _compile_s[event] += secs


def _classify_args(extra, batch: int, m: int, engine="batched"):
    from repro.launch import serve

    return serve.build_parser().parse_args(
        ["--workload", "classify", "--engine", engine, "--batch",
         str(batch), "--m", str(m)] + PRODUCTION + extra)


class Phase:
    """Times one phase and prints its chip readings when it passes."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        _compile_s.clear()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "phase": self.name, "passed": True,
            "chip_reading": {
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": sum(_compile_s.values()),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")},
        }), flush=True)
        return False


def _require(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def phase_thresholds(B: int, m: int):
    from repro.launch import serve

    res = serve.run_classify(_classify_args(THRESHOLDS, B, m))
    _require(res["ok"] == B, f"(a) ok {res['ok']} of {B}")
    _require(res["guarantee_ok"] == B,
             f"(a) E_S(f) <= OPT on {res['guarantee_ok']} of {B}")


def assert_pallas_kernel(args, B: int, m: int):
    """The tree engine's compiled program holds the Pallas kernel, so
    no jnp path stands in for it."""
    import jax

    from repro.core import batched
    from repro.launch import serve

    cls, cfg = serve.classify_config(args)
    shape = (B, cfg.k, m // cfg.k)
    compiled = batched.lower_classify(
        np.zeros(shape + (args.features,), np.float32),
        np.zeros(shape, np.int8), np.ones(shape, bool),
        jax.random.split(jax.random.key(0), B), cfg, cls)
    _require("tpu_custom_call" in compiled.as_text(),
             "(b) no Pallas kernel in the compiled tree engine")


def assert_kernel_matches_reference(players: int, c: int, F: int,
                                    bins: int, nodes: int):
    """The compiled Pallas histogram ≡ a float64 numpy histogram, bit
    for bit, in the single-task and the per-player batched form.  The
    weights sit on the protocol's 2^-23 grid (core/weights.py), where
    every histogram cell is an exact f32 sum."""
    import jax.numpy as jnp

    from repro.kernels.histogram import ops as hist_ops

    rng = np.random.default_rng(0)
    x = ((rng.integers(0, bins, (players, c, F)) + 0.5)
         / bins).astype(np.float32)
    w = (rng.integers(0, 1024, (players, nodes, c))
         * 2.0 ** -23).astype(np.float32)
    wy = w * rng.choice(np.array([-1, 1], np.float32), w.shape)
    b = np.clip(np.floor(x * np.float32(bins)), 0, bins - 1).astype(int)
    onehot = (b[..., None] == np.arange(bins)).astype(np.float64)

    def ref(v):
        return np.einsum("pnc,pcfq->pnfq", v.astype(np.float64), onehot)

    got = hist_ops.node_histograms(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(wy), bins, interpret=False)
    one = hist_ops.node_histograms(jnp.asarray(x[0]), jnp.asarray(w[0]),
                                   jnp.asarray(wy[0]), bins,
                                   interpret=False)
    for g, o, r in zip(got, one, (ref(w), ref(wy))):
        _require(np.array_equal(np.asarray(g, np.float64), r),
                 "(b) batched kernel differs from the reference")
        _require(np.array_equal(np.asarray(o, np.float64), r[0]),
                 "(b) single-task kernel differs from the reference")


def phase_trees(B: int, m: int):
    from repro.launch import serve

    for nodes in (1, 2):
        assert_kernel_matches_reference(16, 512, 28, 32, nodes)
    args = _classify_args(TREES, B, m)
    res = serve.run_classify(args)
    _require(res["guarantee_ok"] == B,
             f"(b) E_S(f) <= OPT on {res['guarantee_ok']} of {B}")
    assert_pallas_kernel(args, B, m)


RESULT_FIELDS = ("hypotheses", "rounds", "ok", "attempts", "alive",
                 "disputed", "min_loss", "hist_stuck", "hist_rounds",
                 "hist_alive", "hist_p", "hist_players", "hist_players_h",
                 "hist_players_last")


def assert_same_results(a, b, what: str):
    for f in RESULT_FIELDS:
        _require(np.array_equal(getattr(a, f), getattr(b, f)),
                 f"{what}: first field that differs: {f}")


def _sorted_disputes(res):
    dx = np.asarray(res.dispute_x)
    order = (np.lexsort(dx.T[::-1]) if dx.ndim == 2
             else np.argsort(dx, kind="stable"))
    pos, neg = (np.asarray(c)[order] for c in res.dispute_y)
    return dx[order], pos, neg


def assert_host_equals_engine(href, got, cls, flat_x, what: str):
    """Host loop ≡ one engine lane: ensemble, disputes, classifier and
    ledger (the per-field order a divergence is reported in)."""
    import jax.numpy as jnp

    from repro.core import classify

    _require(href.attempts == got.attempts, f"{what}: attempts")
    _require(href.rounds == got.rounds, f"{what}: rounds")
    _require(np.array_equal(np.asarray(href.hypotheses)[:href.rounds],
                            np.asarray(got.hypotheses)[:got.rounds]),
             f"{what}: hypotheses")
    for name, h, g in zip(("dispute_x", "dispute_pos", "dispute_neg"),
                          _sorted_disputes(href), _sorted_disputes(got)):
        _require(np.array_equal(h, g), f"{what}: {name}")
    fh = classify.make_classifier(cls, href)
    fg = classify.make_classifier(cls, got)
    _require(np.array_equal(np.asarray(fh(jnp.asarray(flat_x))),
                            np.asarray(fg(jnp.asarray(flat_x)))),
             f"{what}: classifier")
    _require(href.ledger == got.ledger, f"{what}: ledger")


def phase_parity(extra, name: str, m: int, lanes: int):
    """The first ``lanes`` tasks of a phase's deployment, at m points."""
    import jax
    import jax.numpy as jnp

    from repro.core import batched, classify, sharded_batched, tasks
    from repro.launch import serve

    args = _classify_args(extra, lanes, m)
    cls, cfg = serve.classify_config(args)
    x, y, ts = tasks.make_batch(cls, lanes, m, cfg.k, args.noise,
                                seed0=args.seed, scenario=args.scenario)
    keys = jax.random.split(jax.random.key(args.seed), lanes)
    bres = batched.run_accurately_classify_batched(x, y, keys, cfg, cls)
    mesh = sharded_batched.make_players_mesh(cfg.k,
                                             devices=jax.devices()[:1])
    sres = sharded_batched.run_accurately_classify_sharded(
        x, y, keys, cfg, cls, mesh=mesh)
    assert_same_results(bres, sres, f"(c) {name} batched vs sharded")
    for b in range(lanes):
        _require(bool(bres.ok[b]), f"(c) {name} lane {b} not ok")
        href = classify.run_accurately_classify(
            jnp.asarray(x[b]), jnp.asarray(y[b]), keys[b], cfg, cls)
        assert_host_equals_engine(href, bres.per_task(b), cls,
                                  ts[b].flat_x,
                                  f"(c) {name} lane {b} host vs batched")
        sres.validate_ledger(b)


def phase_server(requests: int, m: int):
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--workload", "serve-stream", "--requests", str(requests),
         "--m", str(m), "--trace", "bursty", "--preempt", "0:3",
         "--ckpt-dir", os.path.join(OUT_DIR, "preempt_ckpt")]
        + PRODUCTION)
    res = serve.run_serve_stream(args)
    _require(res["ok"] == requests, f"(d) ok {res['ok']} of {requests}")
    _require(res["steady_compiles"] == 0,
             f"(d) {res['steady_compiles']} steady-state compiles")
    _require(res["resumes"] >= 1, "(d) the preempted dispatch never resumed")


def phase_four_chips(extra, name: str, B: int, m: int):
    """The players mesh over four chips against the batched engine on
    the first chip alone."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import batched, sharded_batched, tasks
    from repro.launch import serve

    args = _classify_args(extra, B, m, engine="sharded")
    cls, cfg = serve.classify_config(args)
    x, y, _ = tasks.make_batch(cls, B, m, cfg.k, args.noise,
                               seed0=args.seed, scenario=args.scenario)
    keys = jax.random.split(jax.random.key(args.seed), B)
    mesh = sharded_batched.make_players_mesh(cfg.k)
    _require(mesh.shape[sharded_batched.AXIS] == 4,
             f"{name}: players mesh over {mesh.shape} devices, not 4")
    players = NamedSharding(mesh, P(None, sharded_batched.AXIS))
    xs, ys = jax.device_put(x, players), jax.device_put(y, players)
    for arr in (xs, ys):
        held = {s.device: s.data.shape[1] for s in arr.addressable_shards}
        _require(len(held) == 4 and set(held.values()) == {cfg.k // 4},
                 f"{name}: players not split over four devices: {held}")
    sres = sharded_batched.run_accurately_classify_sharded(
        xs, ys, keys, cfg, cls, mesh=mesh)
    _require(sres.mesh_devices == 4, f"{name}: mesh_devices "
             f"{sres.mesh_devices}")
    one = jax.devices()[0]
    bres = batched.run_accurately_classify_batched(
        jax.device_put(x, one), jax.device_put(y, one),
        jax.device_put(keys, one), cfg, cls)
    assert_same_results(bres, sres, f"{name}: batched (1 chip) vs "
                        "sharded (4 chips)")
    _require(bool(sres.ok.all()), f"{name}: ok {int(sres.ok.sum())} of {B}")
    for b in range(B):
        sres.validate_ledger(b)
    print(json.dumps({"four_chips": name, "batch": B, "m": m,
                      "mesh_devices": int(sres.mesh_devices),
                      "bit_identical": True,
                      "ledger_validated": f"{B}/{B}"}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # the TPU runtime's own logs go under experiments/ with the rest
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT_DIR, "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    print(json.dumps({"compile_cache": compile_cache.enable()}))
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    os.makedirs(OUT_DIR, exist_ok=True)
    if opts.chips == 4:
        print(json.dumps({"cut": FOUR_CHIPS_CUT}))
        with Phase("four_chips"):
            for deployment in FOUR_CHIPS:
                phase_four_chips(*deployment)
    else:
        for cut in CUTS:
            print(json.dumps({"cut": cut}))
        with Phase("a_thresholds"):
            phase_thresholds(THRESHOLDS_B, THRESHOLDS_M)
        with Phase("b_trees"):
            phase_trees(TREES_B, TREES_M)
        with Phase("c_parity"):
            phase_parity(THRESHOLDS, "thresholds", PARITY_M, PARITY_LANES)
            phase_parity(TREES, "trees", PARITY_M, PARITY_LANES)
        with Phase("d_server"):
            phase_server(48, 1 << 14)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
