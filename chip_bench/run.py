"""Run one cell of the chip benchmark once.

    python3 chip_bench/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for (``BENCHMARK.json``).  Set-up makes the cell's data from the
seed, compiles (or loads from ``<checkout>/.jax_cache``) and warms every
program the cell uses; the window then runs for ``--seconds``; the
plain reference then checks every task the window produced.  With
``--trace 1`` the window runs under the profiler and the line reports
the cell's per-layer metrics, read from the device trace, in place of
its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last,
``compared`` (each number compared, with its limit); the compared
numbers are also the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, or outside a checkout that
holds the program, it exits non-zero and prints no result.  The TPU
runtime's logs and the traces go under ``<checkout>/experiments/
chip_bench``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "experiments", "chip_bench")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"chip_bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no program under {ROOT}/src: run from a checkout")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    if args.workload not in cells:
        return fail(f"no cell {args.workload!r}; cells: {sorted(cells)}")
    chips = cells[args.workload]["chips"]
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT_DIR, "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        return fail(f"cell {args.workload} needs {chips} chips; JAX found "
                    f"{len(devices)}")
    from repro.launch import compile_cache

    compile_cache.enable()
    # cache every program, however quick its compile: set-up then does
    # the same work in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, devices[:chips],
                           OUT_DIR)
    print(json.dumps({"side": out.pop("side")}), flush=True)
    for name, c in out["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
