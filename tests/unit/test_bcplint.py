"""bcplint static-analysis suite (ISSUE 15, tier-1, ``lint`` marker).

Three layers of coverage:

1. **Golden fixtures** — one seeded violation per check under
   ``tests/fixtures/bcplint/``.  Each fixture carries a
   ``# BCPLINT-EXPECT`` marker on the offending line; the test asserts
   the rule fires at exactly that file:line with the expected message.
   If a checks.py refactor stops a rule from firing, this fails before
   the real tree can regress.
2. **Repo-tree clean** — ``run_lint`` over the actual package with the
   checked-in baseline must be clean, every baselined entry justified.
   This is the same invariant CI enforces via the ``bcplint`` script.
3. **Baseline machinery** — unjustified and stale entries are
   themselves failures (the baseline can only shrink honestly).

Pure-AST: nothing here imports jax or the analyzed modules, so the
conftest orders the ``lint`` group first for the cheapest signal.
"""

import os
import shutil
import subprocess
import sys
import time

import pytest

from tools.bcplint.cli import DEFAULT_BASELINE, main as cli_main
from tools.bcplint.engine import parse_baseline, run_lint

pytestmark = pytest.mark.lint

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "bcplint")


def _expect_line(relpath: str, marker: str = "BCPLINT-EXPECT") -> int:
    """1-based line of the seeded violation in a fixture (the marker
    comment sits on the offending line, so the fixtures stay
    self-documenting and the tests never hard-code line numbers)."""
    with open(os.path.join(ROOT, relpath), encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            if marker in line and marker + "-" not in line:
                return i
    raise AssertionError("no %s marker in %s" % (marker, relpath))


def _lint_fixture(name: str, tests_dir=None):
    path = os.path.join(FIXTURES, name)
    return run_lint(ROOT, paths=[path], tests_dir=tests_dir)


def _sole_finding(result, rule):
    matches = [f for f in result.findings if f.rule == rule]
    assert matches, "expected a %s finding, got: %r" % (
        rule, [f.render() for f in result.findings])
    assert len(matches) == 1, [f.render() for f in matches]
    return matches[0]


# ---------------------------------------------------------------------------
# golden fixtures: one seeded violation per check
# ---------------------------------------------------------------------------


def test_bcp001_fires_on_native_family_reemission():
    rel = "tests/fixtures/bcplint/bcp001_telemetry.py"
    f = _sole_finding(_lint_fixture("bcp001_telemetry.py"), "BCP001")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "bcp_fix_depth" in f.message
    assert "native" in f.message


def test_bcp002_fires_on_unpaired_register():
    rel = "tests/fixtures/bcplint/bcp002_pairing.py"
    f = _sole_finding(_lint_fixture("bcp002_pairing.py"), "BCP002")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "'leaky'" in f.message
    assert "unregister" in f.message


def test_bcp003_fires_on_fsync_under_cs_main():
    rel = "tests/fixtures/bcplint/bcp003_blocking.py"
    result = _lint_fixture("bcp003_blocking.py")
    f = _sole_finding(result, "BCP003")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "fsync" in f.message and "cs_main" in f.message
    # the release/.result()/acquire pattern in the same fixture must NOT
    # be flagged — the sole finding above already proves it, but make the
    # intent explicit: no finding anchors on the released .result() call
    assert not any("result" in g.anchor for g in result.findings)


def test_bcp004_fires_on_lock_order_inversion():
    rel = "tests/fixtures/bcplint/bcp004_order.py"
    f = _sole_finding(_lint_fixture("bcp004_order.py"), "BCP004")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "TwoLocks.a_lock" in f.message and "TwoLocks.b_lock" in f.message
    assert "opposite orders" in f.message


def test_bcp005_fires_on_undrilled_fault_site():
    rel = "tests/fixtures/bcplint/bcp005_proj/util/faults.py"
    result = run_lint(
        ROOT, paths=[os.path.join(FIXTURES, "bcp005_proj")],
        tests_dir=os.path.join(FIXTURES, "bcp005_tests"))
    f = _sole_finding(result, "BCP005")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "fixture_untested_site" in f.message
    assert "no test" in f.message


def test_bcp006_fires_on_coercion_and_missing_budget():
    rel = "tests/fixtures/bcplint/bcp006_jit.py"
    result = _lint_fixture("bcp006_jit.py")
    found = [f for f in result.findings if f.rule == "BCP006"]
    assert len(found) == 2, [f.render() for f in result.findings]
    by_line = {f.line: f for f in found}
    coerce = by_line[_expect_line(rel)]
    assert "int(x)" in coerce.message and "traced" in coerce.message
    budget = by_line[_expect_line(rel, "BCPLINT-EXPECT-PROGRAM")]
    assert "fixture_unbudgeted_prog" in budget.message
    assert "shape_budget" in budget.message


# ---------------------------------------------------------------------------
# repo-tree invariant: the actual package is clean under the baseline
# ---------------------------------------------------------------------------


def test_repo_tree_clean_under_baseline():
    result = run_lint(ROOT, baseline_path=DEFAULT_BASELINE)
    assert result.ok, "bcplint regression:\n" + "\n".join(
        [f.render() for f in result.findings]
        + ["stale: " + k for k in result.stale_entries]
        + ["unjustified: " + k for k in result.unjustified_entries]
        + ["%s: %s" % e for e in result.errors])
    # the deliberate designs stay visible, not silently suppressed
    assert result.baselined, "baseline matched nothing — was it emptied?"


def test_every_baseline_entry_is_justified():
    entries = parse_baseline(DEFAULT_BASELINE)
    assert entries, "baseline file is empty"
    missing = [k for k, just in entries.items() if not just]
    assert not missing, "unjustified baseline entries: %r" % missing


# ---------------------------------------------------------------------------
# baseline machinery: unjustified and stale entries are failures
# ---------------------------------------------------------------------------


def test_unjustified_baseline_entry_is_a_failure(tmp_path):
    fixture = os.path.join(FIXTURES, "bcp002_pairing.py")
    raw = run_lint(ROOT, paths=[fixture])
    key = _sole_finding(raw, "BCP002").key
    bl = tmp_path / "baseline"
    bl.write_text(key + "\n")  # no " # why" justification
    result = run_lint(ROOT, paths=[fixture], baseline_path=str(bl))
    assert not result.ok
    assert result.unjustified_entries == [key]


def test_stale_baseline_entry_is_a_failure(tmp_path):
    fixture = os.path.join(FIXTURES, "bcp002_pairing.py")
    raw = run_lint(ROOT, paths=[fixture])
    key = _sole_finding(raw, "BCP002").key
    bl = tmp_path / "baseline"
    bl.write_text(
        key + "  # the seeded leak is deliberate\n"
        "BCP001 no/such/file.py::gone::flat:bcp_x  # stale\n")
    result = run_lint(ROOT, paths=[fixture], baseline_path=str(bl))
    assert not result.ok
    assert not result.findings  # the real finding IS baselined...
    assert result.stale_entries == [  # ...but the dead entry fails the run
        "BCP001 no/such/file.py::gone::flat:bcp_x"]


def test_justified_baseline_suppresses_finding(tmp_path):
    fixture = os.path.join(FIXTURES, "bcp002_pairing.py")
    raw = run_lint(ROOT, paths=[fixture])
    key = _sole_finding(raw, "BCP002").key
    bl = tmp_path / "baseline"
    bl.write_text(key + "  # the seeded leak is deliberate\n")
    result = run_lint(ROOT, paths=[fixture], baseline_path=str(bl))
    assert result.ok
    assert [f.key for f in result.baselined] == [key]


def test_finding_keys_are_line_stable():
    """The baseline key must not embed line numbers — unrelated churn
    above a deliberate design must not invalidate its entry."""
    raw = run_lint(ROOT, paths=[os.path.join(FIXTURES, "bcp002_pairing.py")])
    key = _sole_finding(raw, "BCP002").key
    assert "%d" % _sole_finding(raw, "BCP002").line not in key.split("::")[-1]
    assert key.startswith("BCP002 tests/fixtures/bcplint/bcp002_pairing.py::")


# ---------------------------------------------------------------------------
# CLI: exit codes and the console-script contract
# ---------------------------------------------------------------------------


def test_cli_clean_tree_exits_zero(capsys):
    rc = cli_main(["--root", ROOT])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "bcplint: clean" in out


def test_cli_findings_exit_one(capsys):
    rc = cli_main(["--root", ROOT, "--no-baseline",
                   os.path.join(FIXTURES, "bcp003_blocking.py")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "BCP003" in out


def test_cli_list_checks(capsys):
    assert cli_main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for rule in ("BCP001", "BCP002", "BCP003", "BCP004", "BCP005",
                 "BCP006", "BCP007", "BCP008", "BCP009", "BCP010"):
        assert rule in out


def test_module_invocation_matches_console_script():
    """`python -m tools.bcplint.cli` is the no-install path CI uses."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.bcplint.cli"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "bcplint: clean" in proc.stdout


# ---------------------------------------------------------------------------
# concurrency analysis goldens (ISSUE 18): BCP007-BCP010 + the BCP004
# explicit-acquire blind-spot regression
# ---------------------------------------------------------------------------


def test_bcp004_fires_on_explicit_acquire_release_pairs():
    """Regression for the blind spot: order edges must be minted from
    document-order .acquire()/.release() pairs, not only ``with``."""
    rel = "tests/fixtures/bcplint/bcp004_acquire.py"
    f = _sole_finding(_lint_fixture("bcp004_acquire.py"), "BCP004")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert ("TwoLocksExplicit.a_lock" in f.message
            and "TwoLocksExplicit.b_lock" in f.message)
    assert "opposite orders" in f.message


def test_bcp007_fires_on_no_common_lockset():
    rel = "tests/fixtures/bcplint/bcp007_race.py"
    result = _lint_fixture("bcp007_race.py")
    f = _sole_finding(result, "BCP007")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "RaceBox.latest" in f.message
    assert "RaceBox._writer_a" in f.message
    assert "RaceBox._writer_b" in f.message
    assert "no common lock" in f.message
    # every write site IS under a lock — coverage, not presence, fails;
    # and the per-writer scratch fields (single root each) stay silent
    assert not any("scratch" in g.message for g in result.findings)


def test_bcp008_fires_on_compound_mutations():
    rel = "tests/fixtures/bcplint/bcp008_compound.py"
    result = _lint_fixture("bcp008_compound.py")
    found = [f for f in result.findings if f.rule == "BCP008"]
    assert len(found) == 2, [f.render() for f in result.findings]
    by_line = {f.line: f for f in found}
    aug = by_line[_expect_line(rel)]
    assert "Tally.hits" in aug.message
    assert "read-modify-write" in aug.message
    check = by_line[_expect_line(rel, "BCPLINT-EXPECT-CHECK")]
    assert "Tally.cache" in check.message
    assert "check-then-mutate" in check.message
    # de-overlap: BCP008-flagged attrs must not double-report as BCP007
    assert not any(f.rule == "BCP007" for f in result.findings)


def test_bcp009_fires_on_declared_guard_violation():
    rel = "tests/fixtures/bcplint/bcp009_guarded.py"
    result = _lint_fixture("bcp009_guarded.py")
    f = _sole_finding(result, "BCP009")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "Ledger.total" in f.message and "'cs_lock'" in f.message
    assert "GUARDED_BY" in f.message
    # the compliant write in ok() must not anchor anything
    assert not any("Ledger.ok" in g.anchor for g in result.findings)


def test_bcp009_subset_run_trusts_in_edge_locksets():
    """Linting connman.py alone (the --changed shape) must not flag
    _ban_seq: the RPC roots that reach _snapshot_banlist live in
    rpc/net.py, outside the subset, so BCP009 falls back to the in-edge
    locksets — setban/unban/clear_banned all call it with ban_lock held,
    proving the caller-holds convention locally."""
    path = os.path.join(ROOT, "bitcoincashplus_tpu", "p2p", "connman.py")
    result = run_lint(ROOT, paths=[path])
    assert not any(f.rule == "BCP009" for f in result.findings), \
        [f.message for f in result.findings if f.rule == "BCP009"]


def test_bcp010_fires_on_unjoined_thread():
    rel = "tests/fixtures/bcplint/bcp010_lifecycle.py"
    f = _sole_finding(_lint_fixture("bcp010_lifecycle.py"), "BCP010")
    assert f.path == rel
    assert f.line == _expect_line(rel)
    assert "Leaky._worker" in f.message
    assert "join()" in f.message and "close()" in f.message


def test_bcp010_stays_silent_when_close_joins():
    """The BCP007 fixture joins both threads from close() — its result
    must contain no BCP010 (the credit side of the lifecycle rule)."""
    result = _lint_fixture("bcp007_race.py")
    assert not any(f.rule == "BCP010" for f in result.findings)


# ---------------------------------------------------------------------------
# inline suppression machinery: # BCPLINT-IGNORE[BCP00N]: <why>
# ---------------------------------------------------------------------------

_IGNORE_FIXTURE_SRC = '''\
from concurrent.futures import ThreadPoolExecutor


class T:
    def __init__(self):
        self.pool = ThreadPoolExecutor(max_workers=2)
        self.hits = 0

    def bump(self):
        self.hits += 1  {comment}

    def serve(self):
        self.pool.submit(self.bump)

    def close(self):
        self.pool.shutdown(wait=True)  {stale}
'''


def _ignore_fixture(tmp_path, comment="", stale=""):
    f = tmp_path / "mod.py"
    f.write_text(_IGNORE_FIXTURE_SRC.format(comment=comment, stale=stale))
    return str(f)


def test_justified_inline_ignore_suppresses_finding(tmp_path):
    path = _ignore_fixture(
        tmp_path, comment="# BCPLINT-IGNORE[BCP008]: single-writer pool")
    result = run_lint(str(tmp_path), paths=[path])
    assert result.ok, [f.render() for f in result.findings]
    assert len(result.ignored) == 1
    assert result.ignored[0].rule == "BCP008"


def test_unjustified_inline_ignore_is_a_hard_failure(tmp_path):
    path = _ignore_fixture(tmp_path, comment="# BCPLINT-IGNORE[BCP008]")
    result = run_lint(str(tmp_path), paths=[path])
    assert not result.ok
    assert result.unjustified_ignores == ["mod.py:10 BCP008"]
    # the finding itself survives — an unjustified IGNORE hides nothing
    assert any(f.rule == "BCP008" for f in result.findings)


def test_stale_inline_ignore_is_a_failure(tmp_path):
    path = _ignore_fixture(
        tmp_path, comment="# BCPLINT-IGNORE[BCP008]: single-writer pool",
        stale="# BCPLINT-IGNORE[BCP003]: never fires here")
    result = run_lint(str(tmp_path), paths=[path])
    assert not result.ok
    assert result.stale_ignores == ["mod.py:16 BCP003"]


def test_stale_inline_ignore_tolerated_in_partial_runs(tmp_path):
    """--changed subset runs legitimately miss cross-module findings, so
    staleness proves nothing there (same contract as baseline entries)."""
    path = _ignore_fixture(
        tmp_path, comment="# BCPLINT-IGNORE[BCP008]: single-writer pool",
        stale="# BCPLINT-IGNORE[BCP003]: never fires here")
    result = run_lint(str(tmp_path), paths=[path], partial=True)
    assert result.ok
    assert not result.stale_ignores


def test_docstring_mention_of_ignore_syntax_is_not_a_suppression(tmp_path):
    """Only real COMMENT tokens register — the engine's own docstring
    quotes the syntax and must not create stale entries."""
    f = tmp_path / "mod.py"
    f.write_text('"""Example:\n\n    x += 1  '
                 '# BCPLINT-IGNORE[BCP008]: quoted\n"""\nX = 1\n')
    result = run_lint(str(tmp_path), paths=[str(f)])
    assert result.ok
    assert not result.stale_ignores


# ---------------------------------------------------------------------------
# --changed incremental mode
# ---------------------------------------------------------------------------


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t"] + list(args),
        cwd=cwd, check=True, capture_output=True, timeout=60)


@pytest.fixture
def tiny_repo(tmp_path):
    pkg = tmp_path / "bitcoincashplus_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "clean.py").write_text("X = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    return tmp_path


def test_cli_changed_lints_only_touched_files(tiny_repo, capsys):
    shutil.copy(os.path.join(FIXTURES, "bcp004_acquire.py"),
                tiny_repo / "bitcoincashplus_tpu" / "bad.py")
    rc = cli_main(["--root", str(tiny_repo), "--changed", "HEAD",
                   "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "BCP004" in out and "bad.py" in out
    assert "clean.py" not in out


def test_cli_changed_with_no_changes_exits_zero(tiny_repo, capsys):
    rc = cli_main(["--root", str(tiny_repo), "--changed", "HEAD"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no linted .py files changed" in out


def test_cli_changed_and_paths_are_exclusive(capsys):
    rc = cli_main(["--root", ROOT, "--changed", "HEAD",
                   os.path.join(FIXTURES, "bcp004_acquire.py")])
    assert rc == 2
    assert "exclusive" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the concurrency report is a checked-in, regenerable artifact
# ---------------------------------------------------------------------------


def test_concurrency_report_regenerates_byte_identically():
    from tools.bcplint.race import build_report

    with open(os.path.join(ROOT, "docs", "CONCURRENCY.md"),
              encoding="utf-8") as f:
        committed = f.read()
    assert build_report(ROOT) == committed, (
        "docs/CONCURRENCY.md is stale — regenerate with "
        "`python -m tools.bcplint.cli --concurrency-report > "
        "docs/CONCURRENCY.md`")


def test_concurrency_report_names_known_roots():
    from tools.bcplint.race import build_report

    report = build_report(ROOT)
    for root_name in ("CConnman._run", "ReplicaPool._probe_loop",
                      "SigService._run", "Watchdog._tick_loop"):
        assert root_name in report, root_name
    assert "## Guarded state" in report
    assert "CConnman._banned" in report


# ---------------------------------------------------------------------------
# tier-1 wall budget: the lint stage must never eat the 870 s cap
# ---------------------------------------------------------------------------


def test_full_tree_run_under_wall_budget():
    # CPU seconds, not wall: the lint is single-threaded AST work, and
    # the workers beside this one compile kernels while it runs
    t0 = time.process_time()
    result = run_lint(ROOT, baseline_path=DEFAULT_BASELINE)
    elapsed = time.process_time() - t0
    assert result.ok
    assert elapsed < 10.0, (
        "full-tree bcplint took %.1fs — the 10s budget keeps the "
        "conftest-ordered lint group a cheap first signal" % elapsed)
