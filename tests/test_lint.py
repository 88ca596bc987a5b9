"""repro-lint: AST rules + jaxpr verification (ISSUE 9 tentpole).

Three layers of coverage:

* fixture pairs — every registered rule has a pass fixture (0 findings)
  and a fail fixture (≥1 finding of that rule, non-zero CLI exit);
* the real tree — ``src/`` lints clean with an EMPTY suppressions
  baseline, and the jaxpr audit passes on both engines in every mode;
* mutation tests — un-pinning the histogram kernel's ``_pinned_argmin``
  and deleting wire-counter accumulations in the sharded engine are
  demonstrated to FAIL the lint / audit (the invariants bite, they are
  not decorative).
"""

import os
import subprocess
import sys

import pytest

from tools.repro_lint import engine as E
from tools.repro_lint import rules as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tools", "repro_lint", "fixtures")
BASELINE = os.path.join(REPO, "tools", "repro_lint",
                        "baseline_suppressions.txt")


def _lint_file(path):
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return E.lint_source(src, os.path.relpath(path, REPO), R.ALL_RULES)


def _lint_dir(path):
    kept, _ = E.lint_paths([path], R.ALL_RULES, repo_root=REPO)
    return kept


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------

def test_src_tree_lints_clean():
    kept, suppressed = E.lint_paths(
        [os.path.join(REPO, "src")], R.ALL_RULES, repo_root=REPO,
        baseline=E.load_baseline(BASELINE))
    assert kept == [], "\n".join(str(v) for v in kept)
    assert suppressed == [], (
        "baseline_suppressions.txt must stay EMPTY (repo policy: fixes "
        "land with the rules); suppressed: "
        + "\n".join(str(v) for v in suppressed))


def test_baseline_suppressions_file_is_empty():
    assert E.load_baseline(BASELINE) == set()


# ---------------------------------------------------------------------------
# fixture pairs, one per rule
# ---------------------------------------------------------------------------

SOURCE_RULES = ("RL001", "RL002", "RL003", "RL005", "RL006")


@pytest.mark.parametrize("rid", SOURCE_RULES)
def test_pass_fixture_is_clean(rid):
    found = _lint_file(os.path.join(FIXTURES, f"{rid}_pass.py"))
    assert found == [], "\n".join(str(v) for v in found)


@pytest.mark.parametrize("rid", SOURCE_RULES)
def test_fail_fixture_fires_its_rule(rid):
    found = _lint_file(os.path.join(FIXTURES, f"{rid}_fail.py"))
    assert found, f"{rid}_fail.py produced no findings"
    assert {v.rule for v in found} == {rid}, (
        f"{rid}_fail.py must fail {rid} and only {rid}: "
        + "\n".join(str(v) for v in found))


def test_rl004_pass_fixture_is_clean():
    assert _lint_dir(os.path.join(FIXTURES, "RL004_pass")) == []


def test_rl004_fail_fixture_fires():
    found = _lint_dir(os.path.join(FIXTURES, "RL004_fail"))
    assert found and {v.rule for v in found} == {"RL004"}


def test_every_registered_rule_has_fixture_pair():
    for rid in R.RULE_IDS:
        has_files = all(
            os.path.exists(os.path.join(FIXTURES, f"{rid}_{kind}.py"))
            for kind in ("pass", "fail"))
        has_dirs = all(
            os.path.isdir(os.path.join(FIXTURES, f"{rid}_{kind}"))
            for kind in ("pass", "fail"))
        assert has_files or has_dirs, f"{rid} has no fixture pair"


def test_cli_exit_codes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    ok = subprocess.run(
        [sys.executable, "-m", "tools.repro_lint",
         os.path.join(FIXTURES, "RL001_pass.py")],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "tools.repro_lint",
         os.path.join(FIXTURES, "RL001_fail.py")],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "RL001" in bad.stdout


def test_inline_pragma_suppresses_only_named_rule():
    src = ("# lint-fixture-path: src/repro/core/fixture_pragma.py\n"
           "import jax.numpy as jnp\n"
           "j = jnp.argmin(x)  # repro-lint: allow=RL001 tie-free by "
           "construction\n"
           "k = jnp.argmax(x)  # repro-lint: allow=RL003 wrong rule\n")
    found = E.lint_source(src, "virtual.py", R.ALL_RULES)
    assert [v.rule for v in found] == ["RL001"]
    assert found[0].line == 4


# ---------------------------------------------------------------------------
# jaxpr audit: both engines, every mode
# ---------------------------------------------------------------------------

def test_jaxpr_audit_clean_on_both_engines():
    from tools.repro_lint import jaxpr_audit as A
    failures = A.run_audit()
    assert failures == [], "\n".join(failures)


def test_jaxpr_finalize_smoke():
    from tools.repro_lint import jaxpr_audit as A
    A.finalize_smoke()


def test_collective_census_matches_ledger_declaration():
    """The per-mode expected counts come from ledger.py, not from the
    audit module — a drift in either direction is a failure."""
    from repro.core import ledger
    from tools.repro_lint import jaxpr_audit as A
    tree = A.HistogramTrees(num_features=3, depth=2, bins=8,
                            comm_mode="voting")
    rep = A.audit_case("tree-voting", tree, False, "sharded")
    assert rep.failures == [], "\n".join(rep.failures)
    assert rep.expected == ledger.collective_sites_per_round(tree)
    assert rep.collectives["all_gather"] == 3 + 4 * tree.depth


# ---------------------------------------------------------------------------
# mutation tests: the invariants bite
# ---------------------------------------------------------------------------

def _read(relpath):
    with open(os.path.join(REPO, relpath), encoding="utf-8") as f:
        return f.read()


def test_unpinning_histogram_ref_fails_rl001():
    rel = "src/repro/kernels/histogram/ref.py"
    src = _read(rel)
    assert E.lint_source(src, rel, R.ALL_RULES) == []
    mutated = src.replace("j = _pinned_argmin(flat, F * Q)",
                          "j = jnp.argmin(flat, axis=-1)")
    assert mutated != src, "mutation site moved — update this test"
    found = E.lint_source(mutated, rel, R.ALL_RULES)
    assert any(v.rule == "RL001" for v in found), (
        "reverting to bare jnp.argmin must fail RL001")


def test_monkeypatched_unpin_fails_jaxpr_audit(monkeypatch):
    """Even a runtime unpin (no source change) is caught: the traced
    tree engine then contains the denied `argmin` primitive."""
    import jax.numpy as jnp
    from repro.kernels.histogram import ref
    from tools.repro_lint import jaxpr_audit as A
    monkeypatch.setattr(
        ref, "_pinned_argmin",
        lambda v, size: jnp.argmin(v, axis=-1).astype(jnp.int32))
    # bins=16 (vs the canonical 8): cls is a jit static arg, so this
    # forces a FRESH trace — a config already traced unpatched would be
    # served from the jit cache and hide the mutation
    tree = A.HistogramTrees(num_features=3, depth=2, bins=16,
                            comm_mode="histogram")
    rep = A.audit_case("tree-histogram", tree, False, "sharded")
    assert any("argmin" in f for f in rep.failures), rep.failures


@pytest.mark.parametrize("deleted", [
    "        awire_core = awire_core + out.wire_core\n",
    "        awire_ws = awire_ws + out.wire_ws\n",
])
def test_deleting_wire_accumulation_fails_rl002(deleted):
    rel = "src/repro/core/sharded_batched.py"
    src = _read(rel)
    assert E.lint_source(src, rel, R.ALL_RULES) == []
    mutated = src.replace(deleted, "")
    assert mutated != src, (
        f"accumulation line {deleted!r} moved — update this test")
    found = E.lint_source(mutated, rel, R.ALL_RULES)
    name = deleted.strip().split(" ")[0]
    assert any(v.rule == "RL002" and name in v.message for v in found), (
        f"deleting {name} accumulation must fail RL002: "
        + "\n".join(str(v) for v in found))


def test_removing_collective_site_fails_census(monkeypatch):
    """Dropping a declared collective from the ledger census (the dual
    of adding an unaccounted one to the engine) fails the audit."""
    from repro.core import ledger
    from tools.repro_lint import jaxpr_audit as A
    real = ledger.collective_sites_per_round

    def short_census(cls, *, no_center=False):
        out = dict(real(cls, no_center=no_center))
        out["all_gather"] -= 1     # pretend one site is unaccounted
        return out

    monkeypatch.setattr(ledger, "collective_sites_per_round",
                        short_census)
    cls = A.AxisStumps(num_features=3)
    rep = A.audit_case("stumps", cls, False, "sharded")
    assert any("eqn count" in f for f in rep.failures), rep.failures


# ---------------------------------------------------------------------------
# benchmarks/run.py --list (satellite)
# ---------------------------------------------------------------------------

def test_bench_run_list_exits_zero():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--list"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    from benchmarks.run import EXPECTED_GATES, _suite
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    names = {ln.split(":")[0] for ln in lines}
    assert names == set(_suite())
    for suite, gates in EXPECTED_GATES.items():
        row = next(ln for ln in lines if ln.startswith(suite + ":"))
        for g in gates:
            assert g in row
