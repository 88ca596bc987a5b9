"""Batched serving drivers.

Two workloads share this entry point:

* ``--workload lm`` (default) — prefill a batch of prompts, decode N
  tokens.  CPU-runnable at reduced scale; the same prefill/decode steps
  are what the dry-run lowers at production shapes.
* ``--workload classify`` — serve B independent AccuratelyClassify
  boosting tasks as ONE device dispatch via the batched engine
  (core/batched.py), or, with ``--engine sharded``, over a real
  ``players`` device mesh (core/sharded_batched.py) where the per-round
  coreset/weight-sum exchange is an actual collective and the ledger is
  validated against the measured payloads.  ``--scenario`` picks the
  adversarial noise model (core/scenarios.py): uniform flips, targeted
  flips on the heaviest points, a byzantine player corrupting its whole
  shard, boundary-hugging noise, or drifting noise waves — or an
  *infrastructure* adversary (``dropout``/``flaky``/``rejoin``): a
  player-alive schedule silences ``--infra-player`` mid-protocol and
  the engines proceed with k′ < k players, reporting E_S(f) ≤ OPT over
  the surviving shards and the mask-aware communication ledger.
* ``--workload serve-stream`` — continuous batching: a stream of
  heterogeneous requests (mixed m, noise, scenario) replayed from a
  Poisson or bursty arrival trace through
  :mod:`repro.launch.scheduler`'s shape-bucketed compile cache.
  Reports tasks/sec, p50/p99 latency per bucket, and the cache
  hit/miss/compile counters (steady state after ``--warmup`` must show
  zero compiles).  ``--preempt D:R`` injects a preemption: dispatch D
  is cut off after R rounds, checkpointed to msgpack, requeued and
  resumed bit-identically.

Usage:
    python -m repro.launch.serve --arch qwen3-32b --smoke \
        --batch 4 --prompt-len 64 --gen 16
    python -m repro.launch.serve --workload classify \
        --batch 32 --m 512 --k 4 --noise 2
    python -m repro.launch.serve --workload classify --engine sharded \
        --scenario byzantine --batch 8 --m 512 --k 4
    python -m repro.launch.serve --workload serve-stream \
        --requests 64 --trace poisson --rate 100 --policy pack
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base
from repro.core.pinned import pinned_argmax
from repro.launch import compile_cache
from repro.models import build, frontend
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def run(args) -> dict:
    cfg = base.get_config(args.arch)
    if args.smoke:
        cfg = base.reduced(cfg)
    model = build(cfg)
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    B, P = args.batch, args.prompt_len
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, P)), jnp.int32)}
    if cfg.frontend == "vit_stub":
        batch["prefix_embeds"] = frontend.synth_embeds(
            jax.random.key(1), cfg, B, cfg.frontend_tokens)
    if cfg.encoder_layers:
        batch["frames"] = frontend.synth_embeds(
            jax.random.key(1), cfg, B, P)
    prefill = jax.jit(model.make_prefill_step())
    decode = jax.jit(model.make_decode_step())
    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    tok = pinned_argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for _ in range(args.gen):
        logits, caches = decode(params, caches, tok)
        tok = (pinned_argmax(logits, -1)[:, None]
               % cfg.vocab_size).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    result = {
        "arch": cfg.name, "batch": B, "prompt_len": P,
        "generated": args.gen,
        "prefill_s": round(t_prefill, 3),
        "decode_s_per_token": round(t_decode / max(args.gen, 1), 4),
        "tokens_finite": bool(jnp.all(gen >= 0)),
        "sample": np.asarray(gen[0])[:12].tolist(),
    }
    print(json.dumps(result))
    return result


def classify_config(args):
    """(hypothesis class, BoostConfig) that ``--workload classify``
    runs for these arguments."""
    from repro.core import weak
    from repro.core.types import BoostConfig

    cls = weak.make_class(args.cls, n=args.domain,
                          num_features=args.features,
                          tree_depth=args.tree_depth,
                          tree_bins=args.tree_bins,
                          tree_comm_mode=args.comm_mode,
                          tree_vote_topk=args.vote_topk)
    cfg = BoostConfig(
        k=args.k, coreset_size=args.coreset, domain_size=args.domain,
        opt_budget=args.opt_budget,
        deterministic_coreset=not weak.needs_features(cls))
    return cls, cfg


def run_classify(args) -> dict:
    """Serve a batch of B boosting tasks in one jitted dispatch.

    ``--scenario dropout/flaky/rejoin`` picks an *infrastructure*
    adversary (core/scenarios.InfraSpec): the tasks carry the usual
    ``--noise`` uniform flips, and a player-alive schedule silences
    ``--infra-player`` per the adversary — the engines proceed with
    k′ < k players and the report pins E_S(f) ≤ OPT over the surviving
    shards plus the masked ledger (sharded engine validates it against
    the measured collective payloads).
    """
    from repro.core import batched, scenarios, sharded_batched, tasks

    cls, cfg = classify_config(args)
    B = args.batch
    infra = args.scenario if args.scenario in scenarios.INFRA else None
    noise_scenario = None if infra else args.scenario
    if noise_scenario in scenarios.FEATURE_SCENARIOS:
        _check_feature_scenario(noise_scenario, args)
    x, y, ts = tasks.make_batch(cls, B, args.m, args.k, args.noise,
                                seed0=args.seed,
                                scenario=noise_scenario)
    keys = jax.random.split(jax.random.key(args.seed), B)
    player_sched = None
    spec = None
    if infra:
        spec = scenarios.InfraSpec(
            name=infra, player=args.infra_player,
            drop_round=args.infra_round,
            rejoin_round=args.infra_round + args.infra_gap,
            miss_rate=args.infra_miss_rate)
        player_sched = spec.schedule(args.k, seed=args.seed)
    if args.engine == "sharded":
        run = functools.partial(
            sharded_batched.run_accurately_classify_sharded,
            mesh=sharded_batched.make_players_mesh(args.k))
    else:
        run = batched.run_accurately_classify_batched
    # compile once, then measure the steady-state dispatch
    run(x, y, keys, cfg, cls, player_sched=player_sched)
    t0 = time.time()
    res = run(x, y, keys, cfg, cls, player_sched=player_sched)
    wall = time.time() - t0
    result = {
        "workload": "classify", "engine": args.engine, "batch": B,
        "m": args.m, "k": args.k, "class": args.cls,
        "noise": args.noise, "scenario": args.scenario or "uniform",
        "ok": int(res.ok.sum()), "attempts_max": int(res.attempts.max()),
        "wall_s": round(wall, 4),
        "tasks_per_s": round(B / max(wall, 1e-9), 2),
    }
    if infra:
        reports = [scenarios.infra_report(ts[b], res, b, spec,
                                          seed=args.seed)
                   for b in range(B) if res.ok[b]]
        result["survivors"] = int(spec.survivors(
            args.k, seed=args.seed).sum())
        result["guarantee_ok_survivors"] = int(
            sum(r["guarantee_ok"] for r in reports))
        result["bits_max"] = max((r["bits"] for r in reports), default=0)
    elif args.scenario is not None:
        # the adversary decides how much it corrupts (byzantine flips a
        # whole shard regardless of --noise): report what was planted
        result["noise"] = max(int(t.noise_count) for t in ts)
        reports = [scenarios.scenario_report(ts[b], res, b)
                   for b in range(B) if res.ok[b]]
        result["guarantee_ok"] = int(sum(r["guarantee_ok"]
                                         for r in reports))
        result["recall_contradicted_min"] = round(
            min((r["recall_contradicted"] for r in reports),
                default=1.0), 3)
        result["bits_max"] = max((r["bits"] for r in reports), default=0)
    if args.engine == "sharded":
        validated = 0
        for b in range(B):
            if res.ok[b]:
                res.validate_ledger(b)
                validated += 1
        result["mesh_devices"] = int(res.mesh_devices)
        result["ledger_vs_payload"] = (f"validated_{validated}/{B}"
                                       if validated else "no_ok_lanes")
        result["collective_bytes_max"] = int(res.wire_bytes.max())
    print(json.dumps(result))
    return result


def _check_feature_scenario(name: str, args) -> None:
    """Up-front validation of a planted-concept scenario: needs the
    tree class at sufficient depth — fail at argument time, not deep
    inside task construction (or after a serve-stream cache warm)."""
    from repro.core import scenarios

    if args.cls != "tree":
        raise SystemExit(
            f"--scenario {name} plants a tree concept: run it "
            "with --cls tree (--tree-depth/--tree-bins)")
    need = scenarios.ScenarioSpec(name=name).min_tree_depth()
    if args.tree_depth < need:
        raise SystemExit(
            f"--scenario {name} needs --tree-depth ≥ {need} "
            f"(got {args.tree_depth})")
    if name in ("xor", "checkerboard") and args.features < 2:
        raise SystemExit(
            f"--scenario {name} crosses two features: needs "
            f"--features ≥ 2 (got {args.features})")


def _next_pow2(v: int) -> int:
    return 1 << max(v - 1, 1).bit_length()


def run_serve_stream(args) -> dict:
    """Replay a mixed-shape request stream through the scheduler.

    ``--preempt D:R`` (repeatable) injects an infrastructure failure:
    the D-th dispatch is cut off after R wire rounds, its engine state
    checkpointed to ``--ckpt-dir`` (msgpack), and the batch requeued —
    the resumed completions are still bit-identical to ``one_shot``.
    """
    from repro.core import scenarios
    from repro.launch import scheduler as S

    if args.m % (2 * args.k):
        raise SystemExit(
            f"--m {args.m} must be a multiple of 2*k={2 * args.k}: the "
            "serve-stream shape mix includes m/2, and every shape's k "
            "shards must be equal-sized")
    if args.scenario in scenarios.INFRA:
        raise SystemExit(
            f"--scenario {args.scenario} is an infrastructure adversary "
            "— use --workload classify for player schedules, or "
            "--preempt for serve-stream fault injection")
    n = args.requests
    shapes = [
        {"m": args.m // 2, "noise": 0},
        {"m": args.m, "noise": args.noise},
        {"m": args.m * 2, "noise": args.noise,
         "scenario": args.scenario},
    ]
    preempt = {}
    for spec in args.preempt or []:
        d, r = spec.split(":")
        preempt[int(d)] = int(r)
    if args.trace == "bursty":
        arrivals = S.bursty_trace(n, rate_per_s=args.rate,
                                  burst=args.burst, seed=args.seed)
    else:
        arrivals = S.poisson_trace(n, rate_per_s=args.rate,
                                   seed=args.seed)
    if args.scenario in scenarios.FEATURE_SCENARIOS:
        _check_feature_scenario(args.scenario, args)
    reqs = S.make_request_stream(
        n, arrivals, shapes, seed0=args.seed, k=args.k,
        clsname=args.cls, domain=args.domain,
        num_features=args.features,
        tree_depth=args.tree_depth, tree_bins=args.tree_bins,
        tree_comm_mode=args.comm_mode, tree_vote_topk=args.vote_topk,
        coreset_size=args.coreset, opt_budget=args.opt_budget,
        engine=args.engine)
    # one lattice point per distinct shape: the next power of two over
    # each shape's per-player mloc (deduped, so nearby shapes share)
    lattice = S.BucketLattice(
        b_sizes=(1, 4, 8),
        mloc_sizes=tuple(sorted({_next_pow2(s["m"] // args.k)
                                 for s in shapes})))
    sched = S.BoostScheduler(lattice=lattice, policy=args.policy,
                             fill_wait_s=args.fill_wait,
                             ckpt_dir=args.ckpt_dir if preempt else None,
                             preempt=preempt)
    if args.warmup:
        sched.warm(reqs)                # compile every reachable bucket
    warm = dataclasses.replace(sched.cache.stats)
    done = sched.run_stream(reqs)
    reg = obs_metrics.default_registry()
    obs_metrics.publish_cache_stats(sched.cache.stats, reg)
    obs_metrics.publish_scheduler_stats(sched.stats, reg)
    result = {
        "workload": "serve-stream", "engine": args.engine,
        "trace": args.trace, "policy": args.policy,
        "requests": n, "dispatches": sched.stats.dispatches,
        "padded_requests": sched.stats.padded_requests,
        "filler_lanes": sched.stats.filler_lanes,
        "preemptions": sched.stats.preemptions,
        "resumes": sched.stats.resumes,
        "cache_hits": sched.cache.stats.hits,
        "cache_compiles": sched.cache.stats.compiles,
        "steady_compiles": sched.cache.stats.compiles - warm.compiles,
        "ok": sum(c.ok for c in done),
        **S.latency_summary(done),
    }
    if args.engine == "sharded":
        result["ledger_validated"] = sum(
            bool(c.validate_ledger()) for c in done if c.ok)
    print(json.dumps(result))
    return result


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI surface — exposed so the examples smoke test can
    assert documented flags (e.g. ``--comm-mode``/``--vote-topk``)
    actually parse without running a workload."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm",
                    choices=["lm", "classify", "serve-stream"])
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # classify workload
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--noise", type=int, default=2)
    ap.add_argument("--cls", default="thresholds",
                    choices=["singletons", "thresholds", "intervals",
                             "stumps", "tree"])
    ap.add_argument("--domain", type=int, default=1 << 12)
    ap.add_argument("--coreset", type=int, default=100)
    ap.add_argument("--features", type=int, default=8)
    ap.add_argument("--tree-depth", type=int, default=2,
                    help="--cls tree: tree depth D (2^D leaves)")
    ap.add_argument("--tree-bins", type=int, default=32,
                    help="--cls tree: histogram bins Q (power of two)")
    ap.add_argument("--comm-mode", default="coreset",
                    choices=["coreset", "histogram", "voting"],
                    help="--cls tree: how split finding crosses the "
                         "wire (coreset gather, histogram merge, or "
                         "LightGBM-style parallel voting)")
    ap.add_argument("--vote-topk", type=int, default=2,
                    help="--comm-mode voting: proposals per node per "
                         "player")
    ap.add_argument("--opt-budget", type=int, default=16)
    ap.add_argument("--engine", default="batched",
                    choices=["batched", "sharded"])
    ap.add_argument("--scenario", default=None,
                    choices=[None, "clean", "uniform", "targeted_heavy",
                             "byzantine", "boundary", "drift",
                             "xor", "checkerboard", "bands",
                             "dropout", "flaky", "rejoin"])
    # infrastructure adversaries (--scenario dropout/flaky/rejoin)
    ap.add_argument("--infra-player", type=int, default=1,
                    help="player the infra adversary silences")
    ap.add_argument("--infra-round", type=int, default=5,
                    help="wire round the player first goes absent")
    ap.add_argument("--infra-gap", type=int, default=8,
                    help="rejoin: rounds absent before returning")
    ap.add_argument("--infra-miss-rate", type=float, default=0.3,
                    help="flaky: per-round absence probability")
    # serve-stream workload
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--trace", default="poisson",
                    choices=["poisson", "bursty"])
    ap.add_argument("--rate", type=float, default=100.0)
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--policy", default="pack",
                    choices=["pack", "fill"])
    ap.add_argument("--fill-wait", type=float, default=0.05)
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--preempt", action="append", metavar="D:R",
                    help="preempt dispatch D after R wire rounds "
                         "(repeatable); state checkpoints to --ckpt-dir")
    ap.add_argument("--ckpt-dir", default="experiments/preempt_ckpt")
    # observability (repro/obs): host-span tracing + metrics snapshot
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record host protocol spans and write a "
                         "Chrome/Perfetto trace JSON here (load it at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry (scheduler/cache "
                         "counters, ckpt timing histograms) as JSON")
    return ap


def main():
    args = build_parser().parse_args()
    compile_cache.enable()
    rec = obs_trace.enable() if args.trace_out else None
    try:
        if args.workload == "serve-stream":
            run_serve_stream(args)
        elif args.workload == "classify":
            run_classify(args)
        else:
            run(args)
    finally:
        if rec is not None:
            obs_trace.disable()
            rec.save(args.trace_out)
        if args.metrics_out:
            obs_metrics.default_registry().save(args.metrics_out)


if __name__ == "__main__":
    main()
