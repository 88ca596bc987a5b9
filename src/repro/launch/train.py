"""End-to-end training driver.

CPU-runnable at reduced scale (the quickstart/examples use it); the same
code path lowers to the production mesh when --mesh production is given
(requires real hardware or the dry-run device-count override).

Features: resilient-boosting data weighting + quarantine (the paper's
mechanism as a training flag), AdamW + warmup-cosine, checkpointing,
eval on a held-out clean split.

Usage (CPU):
    python -m repro.launch.train --arch deepseek-7b --smoke \
        --steps 200 --noise 0.1 --resilient
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import base
from repro.core import resilient
from repro.data import DataConfig, SyntheticCorpus
from repro.launch import compile_cache
from repro.models import build
from repro.optim import adamw_init


def run(args) -> dict:
    cfg = base.get_config(args.arch)
    if args.smoke:
        cfg = base.reduced(cfg, d_model=args.d_model, vocab=args.vocab)
    model = build(cfg)
    dc = DataConfig(vocab_size=min(cfg.vocab_size, args.vocab),
                    seq_len=args.seq_len, num_examples=args.num_examples,
                    noise_frac=args.noise, seed=args.seed)
    corpus = SyntheticCorpus(dc)
    params = model.init(jax.random.key(args.seed))
    opt = adamw_init(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    train_step = jax.jit(model.make_train_step(
        lr=args.lr, warmup=max(args.steps // 10, 10),
        total_steps=args.steps))
    rc = resilient.ResilientConfig(
        num_examples=dc.num_examples, check_every=args.check_every,
        coreset_size=args.coreset, min_hits_gap=args.min_gap,
        mw_enabled=args.resilient, quarantine_enabled=args.resilient)
    state = resilient.init_state(rc)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    rng = np.random.default_rng(args.seed)
    history = []
    t0 = time.time()
    for step in range(1, args.steps + 1):
        batch = corpus.batch(rng, args.batch, alive=state.alive)
        w, alive = resilient.batch_weights(state, batch["ids"], rc)
        ids = batch.pop("ids")
        params, opt, met = train_step(
            params, opt, dict(batch, weights=w, alive=alive))
        state = resilient.update(state, ids, met["per_example_nll"],
                                 rc, step)
        if step % args.log_every == 0 or step == args.steps:
            stats = resilient.quarantine_stats(state, corpus.noisy_ids)
            rec = {"step": step, "loss": float(met["loss"]),
                   "grad_norm": float(met["grad_norm"]),
                   "elapsed_s": round(time.time() - t0, 1), **stats}
            history.append(rec)
            print(json.dumps(rec))
        if ckpt and step % args.ckpt_every == 0:
            ckpt.save(step, {"params": params, "opt": opt})
    # clean-split eval: loss on non-noisy examples only
    clean = np.setdiff1d(np.arange(dc.num_examples), corpus.noisy_ids)
    eval_ids = clean[:min(256, clean.size)]
    eb = {
        "tokens": jnp.asarray(corpus.tokens[eval_ids]),
        "labels": jnp.asarray(corpus.labels[eval_ids]),
        "loss_mask": jnp.ones((eval_ids.size, dc.seq_len), jnp.float32),
        "weights": jnp.ones((eval_ids.size,)),
        "alive": jnp.ones((eval_ids.size,)),
    }
    _, em = jax.jit(model.loss_fn)(params, eb)
    result = {
        "arch": cfg.name, "params": int(n_params),
        "steps": args.steps, "resilient": bool(args.resilient),
        "noise": args.noise,
        "final_train_loss": float(met["loss"]),
        "clean_eval_loss": float(em["loss"]),
        **resilient.quarantine_stats(state, corpus.noisy_ids),
        "history": history,
    }
    print(json.dumps({k: v for k, v in result.items()
                      if k != "history"}))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--num-examples", type=int, default=2048)
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--resilient", action="store_true")
    ap.add_argument("--check-every", type=int, default=25)
    ap.add_argument("--coreset", type=int, default=48)
    ap.add_argument("--min-gap", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()
    compile_cache.enable()
    run(args)


if __name__ == "__main__":
    main()
