"""Benchmark harness entry: one benchmark per paper claim.

Prints ``name,us_per_call,derived`` CSV (plus bench-specific fields in
the derived column).  ``python -m benchmarks.run [--only NAME[,NAME…]]``.

Besides ``--out`` (the merged machine-readable results), every run
appends one dated ``BENCH_<n>.json`` snapshot at the repo root — the
perf-trajectory record: n increments monotonically, each file carries
the date, the suites run and their rows, so regressions are diffable
across PRs (the CI bench-smoke job uploads the snapshot as an
artifact).  ``--no-trajectory`` suppresses it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time


def _ensure_src_importable() -> None:
    """Make ``repro`` importable without clobbering the caller's path.

    An existing ``PYTHONPATH=src`` (how CI invokes tier-1 and this
    harness) wins; only when ``repro`` cannot be resolved at all is the
    repo's own ``src/`` appended — resolved once, relative to the repo
    root, never blindly prepended at import time.
    """
    try:
        import repro  # noqa: F401
    except ModuleNotFoundError:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        sys.path.append(os.path.join(repo_root, "src"))


# Correctness gates each suite must EXECUTE (benchmarks/common.gate
# records them).  A gate that stops running — renamed, skipped, its
# suite no longer reaching it — fails the run even though nothing
# asserted: silently-not-run is indistinguishable from passing
# otherwise.  The executed list is printed (and written to
# GITHUB_STEP_SUMMARY in CI) for the record.
EXPECTED_GATES = {
    "batched_classify": ("batched_host_parity",),
    "serving": ("serving_zero_steady_compiles", "serving_one_shot_parity",
                "serving_sharded_ledger_payload"),
    "fault_injection": ("fault_engine_parity", "fault_masked_ledger",
                        "fault_preempt_resume_parity"),
    "checkpointing": ("ckpt_resume_parity", "ckpt_incremental_bytes",
                      "ckpt_template_free_parity"),
    "trees": ("tree_hist_kernel_parity", "tree_xor_guarantee",
              "tree_stump_separation", "tree_matched_accuracy",
              "tree_matched_wire"),
    "tree_comms": ("tree_comm_parity", "tree_comm_ledger",
                   "tree_comm_savings"),
    "streaming": ("streaming_small_m_parity", "streaming_hist_parity",
                  "streaming_peak_memory", "streaming_sketch_epsilon"),
    "observability": ("obs_trace_ledger_exact", "obs_trace_masked",
                      "obs_trace_preempt_resume",
                      "obs_disabled_overhead"),
}


def _suite():
    from benchmarks import (baselines, batched_classify, checkpointing,
                            fault_injection, finite_class, kernel_micro,
                            observability, paper_claims, roofline,
                            serving, sharded_scenarios, streaming,
                            tree_comms, trees)
    return {
        "batched_classify": batched_classify.run_all,
        "serving": serving.run_all,
        "observability": observability.run_all,
        "fault_injection": fault_injection.run_all,
        "checkpointing": checkpointing.run_all,
        "trees": trees.run_all,
        "tree_comms": tree_comms.run_all,
        "sharded_scenarios": sharded_scenarios.run_all,
        "comm_vs_opt": paper_claims.comm_vs_opt,
        "comm_vs_k": paper_claims.comm_vs_k,
        "comm_vs_m": paper_claims.comm_vs_m,
        "comm_vs_d": paper_claims.comm_vs_d,
        "error_guarantee": paper_claims.error_guarantee,
        "lower_bound": paper_claims.lower_bound_bench,
        "resilient_vs_vanilla": baselines.resilient_vs_vanilla,
        "semi_agnostic": baselines.semi_agnostic_bench,
        "neural_resilient": baselines.neural_resilient,
        "finite_class": finite_class.run_all,
        "kernel_micro": kernel_micro.run_all,
        "roofline": roofline.run_all,
        "streaming": streaming.run_all,
    }


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_trajectory_snapshot(all_rows: dict, failures: int,
                              only: str | None,
                              root: str | None = None) -> str:
    """Append the next dated BENCH_<n>.json at the repo root.

    The index is claimed atomically: ``os.open(O_CREAT | O_EXCL)``
    either owns the path or raises, and a collision (two runs in one
    session racing the same glob-derived n, or a leftover file the glob
    missed) retries on the next index — never truncating an existing
    snapshot.
    """
    root = _repo_root() if root is None else root
    taken = []
    for f in glob.glob(os.path.join(root, "BENCH_*.json")):
        m = re.fullmatch(r"BENCH_(\d+)\.json", os.path.basename(f))
        if m:
            taken.append(int(m.group(1)))
    n = max(taken, default=0) + 1
    while True:
        path = os.path.join(root, f"BENCH_{n}.json")
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY,
                         0o644)
            break
        except FileExistsError:
            n += 1
    snapshot = {
        "n": n,
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "only": only,
        "suites_run": sorted(all_rows),
        "failures": failures,
        "results": all_rows,
    }
    with os.fdopen(fd, "w") as f:
        json.dump(snapshot, f, indent=1, default=str)
    return path


def _collect_trend(root: str | None = None) -> dict:
    """bench name → [(snapshot n, date, us_per_call), …] across every
    BENCH_<n>.json at the repo root, in snapshot order."""
    root = _repo_root() if root is None else root
    snaps = []
    for f in glob.glob(os.path.join(root, "BENCH_*.json")):
        m = re.fullmatch(r"BENCH_(\d+)\.json", os.path.basename(f))
        if not m:
            continue
        try:
            with open(f) as fh:
                snap = json.load(fh)
        except (OSError, ValueError):
            continue                     # unreadable snapshot: skip
        snaps.append((int(m.group(1)), snap))
    snaps.sort()
    series: dict = {}
    for n, snap in snaps:
        for suite_name, rows in (snap.get("results") or {}).items():
            if not isinstance(rows, list):
                continue
            for row in rows:
                if not isinstance(row, dict):
                    continue
                try:
                    us = float(row.get("us_per_call"))
                except (TypeError, ValueError):
                    continue
                if us <= 0:              # failed or untimed rows
                    continue
                series.setdefault(row.get("bench", suite_name),
                                  []).append((n, snap.get("date", ""),
                                              us))
    return series


def write_report(tolerance_pct: float = 25.0,
                 root: str | None = None) -> int:
    """Merge the BENCH_<n>.json trajectory into a per-bench trend
    table: latest vs previous snapshot, % delta, regressions beyond
    the tolerance flagged.  Printed to stdout and appended to
    GITHUB_STEP_SUMMARY when CI provides one; returns the number of
    flagged benches (reported, not an exit failure — snapshot-to-
    snapshot wall time is machine-noisy; the correctness gates are the
    hard bar)."""
    series = _collect_trend(root)
    lines = ["| bench | latest µs | prev µs | Δ% | snapshots | flag |",
             "|---|---|---|---|---|---|"]
    flagged = 0
    for bench in sorted(series):
        pts = series[bench]
        _, _, us1 = pts[-1]
        if len(pts) > 1:
            _, _, us0 = pts[-2]
            delta = (us1 - us0) / us0 * 100.0
            flag = "REGRESSED" if delta > tolerance_pct else ""
            flagged += bool(flag)
            lines.append(f"| {bench} | {us1:.0f} | {us0:.0f} "
                         f"| {delta:+.1f}% | {len(pts)} | {flag} |")
        else:
            lines.append(f"| {bench} | {us1:.0f} | — | — | 1 | |")
    table = "\n".join(lines)
    print(table)
    if flagged:
        print(f"# {flagged} bench(es) regressed beyond "
              f"{tolerance_pct:.0f}%", file=sys.stderr)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write(f"## Benchmark trend (tolerance "
                    f"{tolerance_pct:.0f}%)\n\n" + table + "\n")
    return flagged


def _write_gate_summary(suite: dict, gates_executed: dict) -> None:
    """Print the executed-gate table; append it to GITHUB_STEP_SUMMARY
    when CI provides one, so every run records WHICH correctness gates
    actually ran (not just that nothing asserted)."""
    lines = ["| suite | gate | executed | passed |",
             "|---|---|---|---|"]
    for name in suite:
        ran = gates_executed.get(name, {})
        for g in EXPECTED_GATES.get(name, ()):
            lines.append(
                f"| {name} | {g} | {'yes' if g in ran else 'NO'} "
                f"| {'yes' if ran.get(g) else 'NO'} |")
        for g in sorted(set(ran) - set(EXPECTED_GATES.get(name, ()))):
            lines.append(f"| {name} | {g} (unregistered) | yes "
                         f"| {'yes' if ran[g] else 'NO'} |")
    table = "\n".join(lines)
    print(f"# executed gates:\n{table}", file=sys.stderr)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write("## Benchmark correctness gates\n\n" + table + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--out", default="experiments/bench_results.json")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="skip the dated BENCH_<n>.json repo-root "
                         "snapshot")
    ap.add_argument("--list", action="store_true",
                    help="print registered suites and their expected "
                         "gates, then exit 0 (no benchmark runs)")
    ap.add_argument("--report", action="store_true",
                    help="merge the BENCH_<n>.json snapshots into a "
                         "per-bench trend table (latest vs previous, "
                         "%% delta, regressions flagged) and exit — "
                         "no benchmark runs")
    ap.add_argument("--report-tolerance", type=float, default=25.0,
                    metavar="PCT",
                    help="--report: flag benches whose latest "
                         "us_per_call regressed more than PCT%% over "
                         "the previous snapshot (default 25)")
    args = ap.parse_args()
    if args.report:
        write_report(args.report_tolerance)
        return
    _ensure_src_importable()
    from repro.launch import compile_cache
    compile_cache.enable()
    suite = _suite()
    if args.list:
        for name in sorted(suite):
            gates = EXPECTED_GATES.get(name, ())
            print(name if not gates else f"{name}: {' '.join(gates)}")
        return
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in suite]
        if unknown:
            raise SystemExit(
                f"unknown benchmark(s) {unknown}; pick from "
                f"{sorted(suite)}")
        suite = {n: suite[n] for n in names}
    print("name,us_per_call,derived")
    all_rows = {}
    failures = 0
    gates_executed = {}
    from benchmarks import common as _common
    for name, fn in suite.items():
        t0 = time.time()
        _common.reset_gates()
        try:
            rows = fn()
            us = (time.time() - t0) * 1e6
            all_rows[name] = rows
            gates_executed[name] = dict(_common.GATES_RUN)
            # a gate is a regression when it didn't run OR recorded a
            # failure without raising (gate()'s assert is stripped
            # under python -O; the registry must not depend on it)
            missing = [g for g in EXPECTED_GATES.get(name, ())
                       if not _common.GATES_RUN.get(g)]
            if missing:
                failures += 1
                print(f"{name},-1,\"GATES NOT PASSED: {missing}\"")
            for row in rows:
                derived = row.get("derived", "")
                extra = ";".join(f"{k}={v}" for k, v in row.items()
                                 if k not in ("bench", "derived", "cfg",
                                              "cls", "us_per_call"))
                # per-row bench id, not the suite key — a multi-row
                # suite's rows must be tellable apart in the CSV/summary
                print(f"{row.get('bench', name)},"
                      f"{row.get('us_per_call', round(us, 0))},"
                      f"\"{derived};{extra}\"")
        except Exception as e:  # noqa: BLE001
            failures += 1
            gates_executed[name] = dict(_common.GATES_RUN)
            print(f"{name},-1,\"FAILED: {type(e).__name__}: {e}\"")
    _write_gate_summary(suite, gates_executed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.only and os.path.exists(args.out):
        # --only refreshes just its suite's rows; keep the others, but
        # never keep stale rows for a suite that just FAILED (it has no
        # entry in all_rows, so drop any previous one)
        try:
            with open(args.out) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
        for name in suite:
            merged.pop(name, None)
        merged.update(all_rows)
        all_rows = merged
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    if not args.no_trajectory:
        # only suites that actually produced rows; failures are counted
        # in the snapshot's own field, not smuggled in as null results
        path = write_trajectory_snapshot(
            {n: all_rows[n] for n in suite if n in all_rows},
            failures, args.only)
        print(f"# trajectory snapshot: {path}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
