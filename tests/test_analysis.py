"""repro-lint: the analysis framework's own test suite (DESIGN.md §11).

Every rule is held to a paired-fixture contract: a known-bad snippet
under ``tests/fixtures/repro_lint/`` it must flag, and a known-good
twin it must not.  On top of that: suppression-comment semantics
(line, file, ``all``), the CLI's exit codes and JSON shape, the
"repo lints clean" end-to-end run, and (when mypy is installed) the
strict type gate over the annotated core.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (ALL_PASSES, PASS_BY_NAME, lint_repo,
                            run_passes)

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "repro_lint"

# rule name -> fixture basename stem
RULE_FIXTURES = {
    "kernel-contract": "kernel_contract",
    "compat-boundary": "compat_boundary",
    "async-safety": "async_safety",
    "deadline-hook": "deadline_hook",
    "rank-cost-dtype": "rank_dtype",
    "docstring-coverage": "docstring_coverage",
    "doc-links": "doc_links",
    "unused-import": "unused_import",
    "mutable-default": "mutable_default",
    "bare-except": "bare_except",
}


def run_rule(rule, *paths):
    """One rule over explicit paths (scope patterns bypassed)."""
    return run_passes([PASS_BY_NAME[rule]], paths=list(paths))


# ---------------------------------------------------------------------------
# paired fixtures: every rule flags its bad twin, passes its good twin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_bad_fixture_is_flagged(rule):
    report = run_rule(rule, FIXTURES / f"{RULE_FIXTURES[rule]}_bad.py")
    assert report.findings, f"{rule} missed its known-bad fixture"
    assert all(f.rule == rule for f in report.findings)
    assert report.exit_code() == 1


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_good_fixture_is_clean(rule):
    report = run_rule(rule, FIXTURES / f"{RULE_FIXTURES[rule]}_good.py")
    assert not report.findings, (
        f"{rule} false-positived on its known-good fixture:\n"
        + "\n".join(f.render() for f in report.findings))
    assert report.exit_code(strict=True) == 0


def test_kernel_contract_bad_covers_every_clause():
    report = run_rule("kernel-contract",
                      FIXTURES / "kernel_contract_bad.py")
    messages = " ".join(f.message for f in report.findings)
    assert "interpret=" in messages
    assert "grid=" in messages
    assert "int64" in messages
    assert "PAD" in messages


def test_kernel_contract_ops_registration(tmp_path):
    """The ref-oracle clause keys off the ops.py basename."""
    target = tmp_path / "ops.py"
    shutil.copy(FIXTURES / "ops_registration_bad.py", target)
    report = run_rule("kernel-contract", target)
    messages = " ".join(f.message for f in report.findings)
    assert "ref.py oracle" in messages
    assert "forwarding interpret=" in messages
    # the same content under a non-ops basename is out of scope
    clean = run_rule("kernel-contract",
                     FIXTURES / "ops_registration_bad.py")
    assert not clean.findings


def test_deadline_hook_ignores_functions_without_deadline():
    report = run_rule("deadline-hook", FIXTURES / "deadline_hook_good.py")
    assert not report.findings


def test_parse_error_is_reported(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    report = run_rule("bare-except", bad)
    assert [f.rule for f in report.findings] == ["parse-error"]
    assert report.exit_code() == 1


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


def test_line_suppressions_are_honored_and_counted():
    report = run_rule("unused-import", FIXTURES / "suppression_demo.py")
    # json (rule-named) and os (all) suppressed; sys survives
    assert len(report.findings) == 1
    assert "'sys'" in report.findings[0].message
    assert report.suppressed == 2


def test_file_suppression_silences_whole_file():
    report = run_rule("unused-import",
                      FIXTURES / "suppression_file_demo.py")
    assert not report.findings
    assert report.suppressed == 3


def test_suppression_is_rule_specific(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text('"""Doc."""\n'
                   "import os  # repro-lint: disable=bare-except\n")
    report = run_rule("unused-import", src)
    assert len(report.findings) == 1  # wrong rule name: not silenced


# ---------------------------------------------------------------------------
# the repo itself lints clean (the CI gate, in-process)
# ---------------------------------------------------------------------------


def test_repo_lints_clean():
    report = lint_repo()
    assert not report.findings, (
        "repo must lint clean (python -m repro.analysis --strict):\n"
        + "\n".join(f.render() for f in report.findings))
    assert report.exit_code(strict=True) == 0


def test_registry_names_are_unique_and_catalogued():
    assert len(PASS_BY_NAME) == len(ALL_PASSES)
    design = (REPO / "DESIGN.md").read_text()
    for p in ALL_PASSES:
        assert p.scope, f"{p.name} declares no scope"
        assert p.description, f"{p.name} has no description"
        assert f"`{p.name}`" in design, (
            f"rule {p.name} missing from the DESIGN.md §11 catalogue")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=REPO)


def test_cli_exits_nonzero_on_bad_fixture():
    proc = _cli("--rules", "unused-import",
                str(FIXTURES / "unused_import_bad.py"))
    assert proc.returncode == 1
    assert "[unused-import]" in proc.stdout


def test_cli_exits_zero_on_good_fixture():
    proc = _cli("--rules", "unused-import",
                str(FIXTURES / "unused_import_good.py"))
    assert proc.returncode == 0


def test_cli_json_output_shape():
    proc = _cli("--json", "--rules", "mutable-default",
                str(FIXTURES / "mutable_default_bad.py"))
    payload = json.loads(proc.stdout)
    assert payload["findings"]
    assert {"rule", "path", "line", "message", "severity"} <= set(
        payload["findings"][0])


def test_cli_list_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule in RULE_FIXTURES:
        assert rule in proc.stdout


def test_cli_unknown_rule_is_a_usage_error():
    proc = _cli("--rules", "no-such-rule")
    assert proc.returncode == 2
    assert "no-such-rule" in proc.stderr


# ---------------------------------------------------------------------------
# the typed-core gate (runs where mypy is installed, e.g. the CI lint job)
# ---------------------------------------------------------------------------

TYPED_MODULES = [
    "src/repro/core/batch.py",
    "src/repro/core/rank.py",
    "src/repro/kernels/ops.py",
    "src/repro/serving/__init__.py",
    "src/repro/serving/hcpe.py",
    "src/repro/serving/async_server.py",
    "src/repro/serving/registry.py",
]


def test_typed_core_passes_mypy_strict():
    pytest.importorskip("mypy")
    proc = subprocess.run(
        [sys.executable, "-m", "mypy", "--strict", *TYPED_MODULES],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
