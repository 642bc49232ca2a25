"""benchmarks/run.py --json-audit exit-code contract: 0 clean, 1 when the
audit or a linter *fails*, 2 when a lint pass *errors* (crashed tooling
must never look like a green gate)."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import benchmarks.bench_audit as bench_audit  # noqa: E402
import benchmarks.run as run  # noqa: E402


def _record(**overrides):
    base = {"schema_version": bench_audit.SCHEMA_VERSION,
            "audits": [{"passed": True}],
            "kernel_lint": {"findings": [], "passed": True, "error": None},
            "repo_lint": {"findings": [], "passed": True, "error": None},
            "precision": {"findings": [], "passed": True, "error": None},
            "hotloop": {"findings": [], "passed": True, "error": None},
            "lint_errors": [],
            "all_passed": True}
    base.update(overrides)
    return base


def _run_audit(tmp_path, monkeypatch, record):
    monkeypatch.setattr(bench_audit, "audit_json", lambda fast=True: record)
    path = str(tmp_path / "BENCH_audit.json")
    run.main(["--json-audit", path])
    return path


def test_clean_record_exits_zero_and_writes_json(tmp_path, monkeypatch):
    path = _run_audit(tmp_path, monkeypatch, _record())
    with open(path) as f:
        assert json.load(f)["schema_version"] == bench_audit.SCHEMA_VERSION


def test_lint_findings_exit_one(tmp_path, monkeypatch):
    rec = _record(all_passed=False)
    rec["precision"] = {"findings": ["kernel:x: narrow acc"],
                        "passed": False, "error": None}
    with pytest.raises(SystemExit) as e:
        _run_audit(tmp_path, monkeypatch, rec)
    assert e.value.code == 1


def test_crashed_lint_pass_exits_two_not_one(tmp_path, monkeypatch):
    rec = _record(all_passed=False, lint_errors=["hotloop"])
    rec["hotloop"] = {"findings": None, "passed": False,
                     "error": "KeyError: 'labels'"}
    with pytest.raises(SystemExit) as e:
        _run_audit(tmp_path, monkeypatch, rec)
    assert e.value.code == 2


def test_crash_beats_findings_when_both_present(tmp_path, monkeypatch):
    # a record with ordinary findings AND a crashed linter must surface the
    # crash: exit 2 tells CI the tooling is broken, not just the code
    rec = _record(all_passed=False, lint_errors=["precision"])
    rec["precision"] = {"findings": None, "passed": False,
                        "error": "RuntimeError: tracer leak"}
    rec["repo_lint"] = {"findings": ["repro/models/x.py:3: host-sync"],
                        "passed": False, "error": None}
    with pytest.raises(SystemExit) as e:
        _run_audit(tmp_path, monkeypatch, rec)
    assert e.value.code == 2


def test_json_still_written_before_nonzero_exit(tmp_path, monkeypatch):
    # CI uploads BENCH_audit.json with if: always() — the record must land
    # on disk even when the gate fails
    rec = _record(all_passed=False, lint_errors=["kernel_lint"])
    rec["kernel_lint"] = {"findings": None, "passed": False,
                          "error": "ValueError: boom"}
    path = str(tmp_path / "BENCH_audit.json")
    monkeypatch.setattr(bench_audit, "audit_json", lambda fast=True: rec)
    with pytest.raises(SystemExit):
        run.main(["--json-audit", path])
    with open(path) as f:
        assert json.load(f)["lint_errors"] == ["kernel_lint"]


def test_raising_bench_exits_nonzero(monkeypatch, capsys):
    # a bench that raised still gets its ERROR row, but the run fails
    import benchmarks.bench_kernels as bench_kernels

    def boom(fast=True):
        raise RuntimeError("kernel refused")
    monkeypatch.setattr(bench_kernels, "run", boom)
    with pytest.raises(SystemExit) as e:
        run.main(["--only", "kernels"])
    assert e.value.code == 1
    assert "kernels,0.0,ERROR:RuntimeError:kernel refused" in \
        capsys.readouterr().out
