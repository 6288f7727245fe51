"""Tier-1 gate: ``rtpu lint`` must run CLEAN over the runtime's own
source. Every finding is either fixed, inline-annotated with a reason,
or carried in the reviewed baseline (``ray_tpu/analysis/baseline.json``
— every entry has a reviewer reason, and stale entries fail here until
pruned, so baselined counts only go down).

The fixture suite proving each checker catches its seeded violation is
``tests/test_analysis.py``; this file only gates the real tree plus
the stability of the machine interfaces (JSON schema, --changed-only).
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from ray_tpu.analysis import (default_baseline_path, format_json,
                              run_lint)

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def report():
    return run_lint(REPO_ROOT)


def _describe(findings):
    return "\n".join(
        f"  {f.path}:{f.line}: {f.checker} [{f.severity}] {f.message}"
        for f in findings)


def test_repo_is_lint_clean(report):
    assert not report.findings, (
        "rtpu lint found unsuppressed issues — fix them, annotate with "
        "a reason, or (for reviewed-and-accepted findings) baseline "
        "them:\n" + _describe(report.findings))


def test_no_stale_baseline_entries(report):
    assert report.stale_baseline == [], (
        "baseline entries no longer match any finding — the underlying "
        "issue was fixed, so prune these from "
        "ray_tpu/analysis/baseline.json (counts only go down):\n  "
        + "\n  ".join(report.stale_baseline))


def test_every_baseline_entry_has_a_reviewer_reason():
    raw = json.loads(default_baseline_path(REPO_ROOT).read_text())
    assert raw["version"] == 1
    for key, entry in raw["entries"].items():
        assert entry.get("count", 0) >= 1, key
        reason = entry.get("reason", "")
        assert reason and not reason.startswith("TODO"), (
            f"baseline entry needs a real reviewer reason: {key}")


def test_all_checker_families_ran(report):
    families = {cid[0] for cid in report.checkers_run}
    # C=concurrency, E=exceptions, D=device, I=invariants.
    assert families == {"C", "E", "D", "I"}, report.checkers_run


def test_invariant_site_tables_still_bind():
    """Every file named by a site table must exist — a path rename
    must move the table row, not silently retire its coverage."""
    from ray_tpu.analysis import invariants as inv
    for tables in (inv.EVENT_SITE_TABLES, inv.GAUGE_SITE_TABLES,
                   inv.REF_SITE_TABLES, inv.PERF_SITE_TABLES,
                   inv.FLIGHTREC_SITE_TABLES, inv.SPEC_SITE_TABLES):
        for path, _needle, _entries, _why in tables:
            assert (REPO_ROOT / path).is_file(), path
    for path, _banned, _why in inv.LAYER_TABLES:
        assert (REPO_ROOT / path).is_dir(), path
    for path in inv.PRIVATE_IMPORT_PACKAGES:
        assert (REPO_ROOT / path).is_dir(), path


def test_json_schema_is_stable(report):
    """Machine consumers pin this shape; extending is fine, renaming
    or removing keys is a breaking change bump ``JSON_SCHEMA_VERSION``."""
    doc = json.loads(format_json(report))
    assert doc["version"] == 1
    assert set(doc) == {"version", "summary", "files_checked",
                        "checkers", "findings", "stale_baseline"}
    assert set(doc["summary"]) == {"total", "suppressed",
                                   "stale_baseline", "by_severity"}
    # Finding dict shape (probe with one synthetic finding).
    from ray_tpu.analysis import Finding
    f = Finding(checker="C101", family="concurrency", severity="P0",
                path="x.py", line=1, col=0, message="m")
    assert set(f.to_dict()) == {"checker", "family", "severity", "path",
                                "line", "col", "symbol", "message",
                                "snippet", "key"}


def test_changed_only_is_a_subset(report):
    rep = run_lint(REPO_ROOT, changed_only=True)
    assert rep.files_checked <= report.files_checked
    assert not rep.findings, _describe(rep.findings)
    # Restricted runs never report staleness (they only prove a subset).
    assert rep.stale_baseline == []


def test_cli_lint_runs_clean():
    # Scoped to one package: this proves the CLI wiring (exit code,
    # summary line); full-repo cleanliness is gated in-process above.
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint",
         "ray_tpu/analysis"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_lint_json_mode():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint",
         "--format", "json", "ray_tpu/analysis"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["summary"]["total"] == 0
