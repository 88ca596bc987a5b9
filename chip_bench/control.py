"""The controls: the program run as a tempting change would run it.

    python3 chip_bench/control.py --workload <cell> --control <name> \
        --seeds 1,2,3 --seconds <s>

Runs a cell as ``run.py`` does, once per seed in one process, with the
program changed underneath and judged by ``harness.judge`` as every run
is.  Prints one JSON line per seed with the compared numbers and
``correct``.  Needs the chips the cell asks for.  The controls:

* ``rounds``: half the paper's round count (``rounds_factor`` 3 in
  place of 6: T = ⌈3·log2 m⌉), the cut that would halve device time.
* ``high``: the histogram kernel's matmul at ``Precision.HIGH`` (three
  bfloat16 passes) in place of ``HIGHEST``.
* ``bf16``: the histogram kernel's matmul on bfloat16 operands (one
  pass), the multiplicative-weights sums, mixture and sampling
  log-probabilities rounded to bfloat16, and the centre's ERM weights
  taken in bfloat16 without the 2^-23 grid.
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist_kernel_at(precision, bins, qp, xt_ref, lhs_ref, out_ref):
    """The histogram kernel's body with the matmul at ``precision``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from repro.kernels.histogram.ref import bin_index

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = bin_index(xt_ref[...], bins)
    bf, bc = b.shape
    q = jax.lax.broadcasted_iota(jnp.int32, (bf, qp, bc), 1)
    onehot = (b[:, None, :] == q).astype(jnp.float32).reshape(bf * qp, bc)
    out_ref[...] += jax.lax.dot_general(
        lhs_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _bf16(fn):
    """``fn`` with its result rounded to bfloat16 (and back)."""
    import jax.numpy as jnp

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        return fn(*a, **kw).astype(jnp.bfloat16).astype(jnp.float32)
    return wrapped


@contextlib.contextmanager
def precision_control(name: str):
    """Patch the program's histogram kernel (and, for ``bf16``, its
    weight arithmetic) for the duration; restores it after."""
    import jax
    import jax.numpy as jnp
    from repro.core import weights
    from repro.kernels.histogram import kernel

    saved = {(kernel, "_hist_kernel"): kernel._hist_kernel}
    prec = jax.lax.Precision.HIGH if name == "high" else \
        jax.lax.Precision.DEFAULT
    kernel._hist_kernel = functools.partial(_hist_kernel_at, prec)
    if name == "bf16":
        for attr in ("log_weight_sum", "mixture_weights",
                     "normalized_log_probs"):
            saved[(weights, attr)] = getattr(weights, attr)
            setattr(weights, attr, _bf16(getattr(weights, attr)))
        saved[(weights, "erm_weights")] = weights.erm_weights
        weights.erm_weights = lambda mix, c: (
            mix.astype(jnp.bfloat16) / c).astype(jnp.float32)
    jax.clear_caches()
    try:
        yield
    finally:
        for (mod, attr), fn in saved.items():
            setattr(mod, attr, fn)
        jax.clear_caches()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", choices=("rounds", "high", "bf16"),
                    required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    compile_cache.enable()
    import harness

    chips = harness.Cell.load(ROOT, args.workload).entry["chips"]
    overrides = {"rounds_factor": 3} if args.control == "rounds" else None
    patch = (contextlib.nullcontext() if args.control == "rounds"
             else precision_control(args.control))
    with patch:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = harness.run_cell(
                ROOT, args.workload, seed, args.seconds, False,
                time.perf_counter(), devices[:chips],
                os.path.join(ROOT, "experiments", "chip_bench"),
                program_overrides=overrides)
            print(json.dumps({"control": args.control, "seed": seed,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
