"""Tier-1 gate: tpumnist-lint is clean over the codebase it guards.

The contract (ISSUE 5): ``python -m tools.analyzer`` over
``pytorch_distributed_mnist_tpu/`` and ``tools/`` exits 0
with ZERO non-baselined findings; every baseline entry carries a
justification; a stale baseline entry fails the gate; and deliberately
re-introducing the zlib-strand bug (narrowing ``_try_load``'s except
back to a tuple) makes the analyzer fail with a file:line finding.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from tools.analyzer import (  # noqa: E402
    analyze_snippet,
    default_baseline_path,
    load_baseline,
    run_analysis,
)

pytestmark = pytest.mark.lint

GATE_PATHS = [os.path.join(_REPO, p)
              for p in ("pytorch_distributed_mnist_tpu", "tools")]

# One full-tree analysis shared by every read-only assertion below (a
# cold run costs ~7s of tier-1 wall on one core; four tests reading the
# same immutable result need not repeat it).
_GATE_RESULT = None


def _gate_result():
    global _GATE_RESULT
    if _GATE_RESULT is None:
        _GATE_RESULT = run_analysis(GATE_PATHS)
    return _GATE_RESULT


def test_codebase_has_zero_nonbaselined_findings():
    result = _gate_result()
    rendered = "\n".join(f.render() for f in result.findings)
    assert result.ok, (
        f"tpumnist-lint found unbaselined violations (fix them — only "
        f"genuinely intentional findings may be baselined, with a "
        f"justification):\n{rendered}\n"
        f"stale: {result.stale_baseline}\n"
        f"baseline problems: {result.baseline_problems}")
    # The gate is only meaningful if it actually scanned the codebase.
    assert result.n_files > 50, result.n_files


def test_every_baseline_entry_has_a_justification():
    path = default_baseline_path()
    entries, problems = load_baseline(path)
    assert not problems, problems
    raw = json.loads(pathlib.Path(path).read_text())
    assert len(raw) == len(entries)  # nothing skipped by validation
    for entry in entries:
        assert str(entry["justification"]).strip(), entry


def test_baseline_suppressions_each_match_exactly_one_known_finding():
    """The baseline documents ACCEPTED findings — each entry must still
    be suppressing something (stale entries fail), and what it
    suppresses is visible in the result for audit."""
    result = _gate_result()
    assert not result.stale_baseline, result.stale_baseline
    suppressed_checkers = {f.checker for f, _e in result.suppressed}
    entries, _ = load_baseline(default_baseline_path())
    assert len(result.suppressed) >= len(entries)
    for entry in entries:
        assert entry["checker"] in suppressed_checkers


_CLI = pathlib.Path(_REPO) / "pytorch_distributed_mnist_tpu" / "cli.py"


def _try_load_region(source: str) -> str:
    start = source.index("def _try_load")
    return source[start:source.index("loaded = (_try_load")]


def test_reintroducing_the_zlib_strand_fails_the_gate():
    """Narrow ``_try_load``'s funnel back to an enumerated tuple — the
    exact PR-1-era bug — and the agreement-except-breadth checker must
    produce a file:line finding in the dataset-agreement scope."""
    source = _CLI.read_text()
    region = _try_load_region(source)
    assert re.search(r"except Exception\b", region), (
        "cli.py _try_load no longer catches Exception — if that is "
        "intentional, this acceptance test and the checker must evolve "
        "together")
    narrowed = source.replace(
        region,
        region.replace(
            "except Exception as exc:",
            "except (FileNotFoundError, ValueError, OSError, "
            "EOFError) as exc:", 1),
        1)
    assert narrowed != source
    findings = analyze_snippet(narrowed,
                               checkers=["agreement-except-breadth"],
                               filename="cli.py")
    assert findings, "narrowed _try_load funnel was not flagged"
    f = findings[0]
    assert f.symbol == "_build_loaders"
    assert f.line > 0 and f.path == "cli.py"  # file:line attribution
    assert "zlib" in f.message  # names the incident class


def test_pristine_cli_is_clean_for_the_breadth_checker():
    findings = analyze_snippet(_CLI.read_text(),
                               checkers=["agreement-except-breadth"],
                               filename="cli.py")
    assert findings == []


def test_stale_baseline_entry_fails_the_gate(tmp_path):
    target = tmp_path / "clean.py"
    target.write_text("def f():\n    return 1\n")
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([{
        "checker": "lock-discipline",
        "path": "clean.py",
        "contains": "no longer exists",
        "justification": "was accepted once; the code is gone",
    }]))
    result = run_analysis([str(target)], baseline=str(baseline))
    assert not result.ok
    assert len(result.stale_baseline) == 1
    assert result.findings == []  # clean code; ONLY the staleness fails


def test_cli_entry_point_exits_zero_and_emits_schema_json():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analyzer", "--format", "json"]
        + GATE_PATHS,
        capture_output=True, text=True, cwd=_REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["summary"]["ok"] is True
    assert payload["summary"]["findings"] == 0
    # The lock-discipline report must include the engine/pool lock graph
    # (ISSUE 5 acceptance).
    graph = payload["reports"]["lock-discipline"]["lock_graph"]
    engine = graph["pytorch_distributed_mnist_tpu/serve/engine.py"]
    # The staging free-list lock lives on the shared StagingPool since
    # ISSUE 12 (the MPMD plane reuses the same lifecycle).
    assert set(engine["locks"]) == {"InferenceEngine._lock",
                                    "StagingPool._lock"}
    pool = graph["pytorch_distributed_mnist_tpu/serve/pool.py"]
    assert pool["locks"] == ["EnginePool._lock"]


def test_gate_runs_all_twelve_checkers():
    """Analyzer v2 contract: the default registry carries the five
    serve/distrib-era checkers alongside the original seven — the gate
    above is only as strong as this list."""
    from tools.analyzer import checker_registry

    assert list(checker_registry()) == [
        "collective-symmetry", "agreement-except-breadth",
        "trace-purity", "recompile-hazard", "lock-discipline",
        "registry-drift", "marker-registry",
        "thread-lifecycle", "handler-discipline",
        "generation-ordering", "short-read", "donated-reuse",
    ]
    result = _gate_result()
    assert set(result.checkers) == set(checker_registry())


def test_sarif_output_is_schema_shaped():
    """Pin the SARIF 2.1.0 surface CI uploaders rely on: version, tool
    driver with one rule per checker, results with physical locations,
    and baselined findings carried as external suppressions."""
    from tools.analyzer import checker_registry, render_sarif

    result = _gate_result()
    payload = json.loads(render_sarif(result))
    assert payload["version"] == "2.1.0"
    assert payload["$schema"].endswith("sarif-2.1.0.json")
    (run,) = payload["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "tpumnist-lint"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert rule_ids == set(checker_registry())
    for rule in driver["rules"]:
        assert rule["shortDescription"]["text"]
    # The gate is clean, so every emitted result is a suppressed
    # baseline entry — and each must carry its justification.
    for res in run["results"]:
        assert res["ruleId"] in rule_ids
        loc = res["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        if "suppressions" in res:
            (sup,) = res["suppressions"]
            assert sup["kind"] == "external"
            assert sup["justification"].strip()
    suppressed = [r for r in run["results"] if "suppressions" in r]
    assert len(suppressed) == len(result.suppressed)


def test_warm_cache_rerun_is_deterministic(tmp_path):
    """Two runs over the same tree with the same cache file: identical
    findings byte-for-byte, and the second run reports a cache hit."""
    cache = str(tmp_path / "cache.json")
    cold = run_analysis(GATE_PATHS, cache=cache)
    assert cold.cache_info is not None and cold.cache_info["hit"] is False
    warm = run_analysis(GATE_PATHS, cache=cache)
    assert warm.cache_info is not None and warm.cache_info["hit"] is True
    cold_payload = [f.render() for f in cold.findings] + \
        [f.render() for f, _ in cold.suppressed]
    warm_payload = [f.render() for f in warm.findings] + \
        [f.render() for f, _ in warm.suppressed]
    assert cold_payload == warm_payload
    assert warm.ok == cold.ok


def test_cache_invalidates_on_file_change(tmp_path):
    """Touching one byte of one analyzed file must flip the next run
    back to a cold (correct) analysis, not replay stale findings."""
    target = tmp_path / "mod.py"
    target.write_text("import subprocess\n\n"
                      "def go(cmd):\n"
                      "    p = subprocess.Popen(cmd)\n"
                      "    return p.pid\n")
    cache = str(tmp_path / "cache.json")
    first = run_analysis([str(target)], baseline=None, cache=cache)
    assert len(first.findings) == 1  # unreaped Popen
    target.write_text("import subprocess\n\n"
                      "def go(cmd):\n"
                      "    with subprocess.Popen(cmd) as p:\n"
                      "        return p.wait()\n")
    second = run_analysis([str(target)], baseline=None, cache=cache)
    assert second.cache_info is not None
    assert second.cache_info["hit"] is False
    assert second.findings == []


def test_cli_nonexistent_path_is_a_usage_error_exit_2():
    """Exit-code contract: 2 for a misconfigured invocation (typoed
    path), distinct from 1 (real lint findings) for CI wrappers."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analyzer", "/nonexistent_path_xyz"],
        capture_output=True, text=True, cwd=_REPO, timeout=120)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "does not exist" in proc.stdout
