"""Baseline round-trip and JSON output schema stability.

Round-trip: a finding appears -> a baseline entry suppresses it (run
goes green) -> the code is fixed -> the now-stale entry fails the run.
Plus: entries without justifications are config errors, and the JSON
schema the CI/report consumers parse is pinned key-for-key.
"""

import json

import pytest


from tools.analyzer import (  # noqa: E402
    SCHEMA_VERSION,
    load_baseline,
    run_analysis,
)

pytestmark = pytest.mark.lint

_VIOLATION = """\
import threading, jax

class Engine:
    def __init__(self):
        self._lock = threading.Lock()

    def swap(self, params):
        with self._lock:
            self._params = jax.device_put(params)
"""

_FIXED = """\
import threading, jax

class Engine:
    def __init__(self):
        self._lock = threading.Lock()

    def swap(self, params):
        placed = jax.device_put(params)
        with self._lock:
            self._params = placed
"""


def test_baseline_roundtrip_add_suppress_stale(tmp_path):
    target = tmp_path / "engine_twin.py"
    target.write_text(_VIOLATION)

    # 1. The finding appears (no baseline).
    result = run_analysis([str(target)], baseline=None)
    assert len(result.findings) == 1
    finding = result.findings[0]
    assert finding.checker == "lock-discipline"

    # 2. Baseline it (triaged-accepted, justified): run goes green and
    #    the suppression is attributed to the entry.
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": finding.checker,
        "path": finding.path,
        "contains": "device_put",
        "justification": "twin fixture: accepted for the round-trip test",
    }]))
    result = run_analysis([str(target)], baseline=str(baseline))
    assert result.ok
    assert result.findings == []
    assert len(result.suppressed) == 1
    assert result.suppressed[0][1]["contains"] == "device_put"

    # 3. Fix the code: the entry is now stale and FAILS the run — the
    #    baseline can only shrink, never rot.
    target.write_text(_FIXED)
    result = run_analysis([str(target)], baseline=str(baseline))
    assert not result.ok
    assert result.findings == []
    assert len(result.stale_baseline) == 1

    # 4. Delete the entry: green again.
    baseline.write_text("[]")
    result = run_analysis([str(target)], baseline=str(baseline))
    assert result.ok


def test_subset_run_does_not_condemn_out_of_set_entries(tmp_path):
    """Linting a path subset must not report entries for files the run
    never analyzed as stale — ``tools/analyzer some/file.py`` is an
    advertised usage and must stay green on a clean file."""
    violating = tmp_path / "engine_twin.py"
    violating.write_text(_VIOLATION)
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")

    result = run_analysis([str(violating)], baseline=None)
    (finding,) = result.findings
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": finding.checker,
        "path": finding.path,
        "contains": "device_put",
        "justification": "twin fixture: accepted for the subset test",
    }]))

    # Subset that excludes the baselined file: entry is NOT judged.
    result = run_analysis([str(clean)], baseline=str(baseline))
    assert result.ok and result.stale_baseline == []

    # Full set including the (still-violating) file: entry is used.
    result = run_analysis([str(clean), str(violating)],
                          baseline=str(baseline))
    assert result.ok and len(result.suppressed) == 1

    # Fix the file and analyze it: NOW the unused entry is stale.
    violating.write_text(_FIXED)
    result = run_analysis([str(violating)], baseline=str(baseline))
    assert not result.ok and len(result.stale_baseline) == 1


def test_baseline_entry_without_justification_is_a_problem(tmp_path):
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": "lock-discipline", "path": "x.py",
        "contains": "anything", "justification": "  ",
    }]))
    entries, problems = load_baseline(str(baseline))
    assert entries == []
    assert len(problems) == 1 and "justification" in problems[0]

    target = tmp_path / "clean.py"
    target.write_text("x = 1\n")
    result = run_analysis([str(target)], baseline=str(baseline))
    assert not result.ok  # a malformed baseline fails the gate loudly


def test_missing_explicit_baseline_is_a_problem(tmp_path):
    target = tmp_path / "clean.py"
    target.write_text("x = 1\n")
    result = run_analysis([str(target)],
                          baseline=str(tmp_path / "absent.json"))
    assert not result.ok
    assert result.baseline_problems


def test_parse_error_findings_cannot_be_baselined(tmp_path):
    target = tmp_path / "broken.py"
    target.write_text("def f(:\n")
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": "parse-error", "path": "broken.py",
        "contains": "could not parse",
        "justification": "trying to hide a syntax error",
    }]))
    result = run_analysis([str(target)], baseline=str(baseline))
    assert not result.ok
    assert result.baseline_problems  # the entry itself is rejected
    assert any(f.checker == "parse-error" for f in result.findings)


# -- JSON schema stability ---------------------------------------------------

_TOP_KEYS = {"schema_version", "paths", "checkers", "findings",
             "suppressed", "stale_baseline", "baseline_problems",
             "reports", "cache", "summary"}
_FINDING_KEYS = {"checker", "path", "line", "col", "message", "hint",
                 "symbol"}
_SUMMARY_KEYS = {"files", "findings", "suppressed", "stale_baseline", "ok"}


def test_json_output_schema_is_stable(tmp_path):
    target = tmp_path / "engine_twin.py"
    target.write_text(_VIOLATION)
    payload = run_analysis([str(target)], baseline=None).to_dict()
    assert payload["schema_version"] == SCHEMA_VERSION == 1
    assert set(payload) == _TOP_KEYS
    assert set(payload["summary"]) == _SUMMARY_KEYS
    assert payload["findings"], "fixture should produce one finding"
    for f in payload["findings"]:
        assert set(f) == _FINDING_KEYS
        assert isinstance(f["line"], int) and f["line"] > 0
    # suppressed rows are findings + the justification that excused them
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": "lock-discipline", "path": payload["findings"][0]["path"],
        "contains": "device_put", "justification": "schema fixture",
    }]))
    payload = run_analysis([str(target)],
                           baseline=str(baseline)).to_dict()
    for row in payload["suppressed"]:
        assert set(row) == _FINDING_KEYS | {"justification"}
    # the lock graph report keeps its shape
    graph = payload["reports"]["lock-discipline"]["lock_graph"]
    (mod_report,) = graph.values()
    assert set(mod_report) == {"locks", "order_edges"}


def test_json_output_is_deterministic(tmp_path):
    target = tmp_path / "engine_twin.py"
    target.write_text(_VIOLATION)
    a = run_analysis([str(target)], baseline=None).to_dict()
    b = run_analysis([str(target)], baseline=None).to_dict()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
