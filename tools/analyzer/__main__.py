"""CLI for tpumnist-lint: ``python -m tools.analyzer [options] [paths]``.

Exit codes: 0 clean (baselined findings allowed), 1 findings / stale or
invalid baseline entries, 2 usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# Standalone invocation from anywhere: the repo root (two levels up) must
# be importable for the absolute ``tools.analyzer`` imports.
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.analyzer import (  # noqa: E402
    checker_registry,
    default_cache_path,
    render_sarif,
    render_text,
    run_analysis,
)

#: What the tier-1 gate analyzes when no paths are given (tools/lint.sh
#: and tests/test_analyzer_gate.py pin the same set).
DEFAULT_PATHS = ("pytorch_distributed_mnist_tpu", "tools")


def _git_changed_files():
    """Modified + untracked .py files from git, repo-root relative
    absolute paths; None when git is unavailable (not a checkout)."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain"], cwd=_REPO,
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    files = []
    for line in out.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:].strip()
        if " -> " in path:  # rename: the new side is what exists now
            path = path.split(" -> ", 1)[1]
        path = path.strip('"')
        if path.endswith(".py"):
            files.append(os.path.join(_REPO, path))
    return files


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tools.analyzer",
        description="tpumnist-lint: AST invariant checker (collective "
                    "symmetry, agreement except-breadth, trace purity, "
                    "recompile hazards, lock discipline, registry "
                    "drift, thread lifecycle, handler discipline, "
                    "generation ordering, short reads, donated reuse)",
    )
    p.add_argument("paths", nargs="*", default=None,
                   help=f"files/directories to analyze (default: "
                        f"{' '.join(DEFAULT_PATHS)}, resolved from the "
                        f"repo root)")
    p.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="baseline file of triaged-accepted findings "
                        "(default: tools/analyzer/baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline: report every finding")
    p.add_argument("--checkers", default=None, metavar="ID[,ID...]",
                   help="run only these checkers")
    p.add_argument("--list-checkers", action="store_true")
    p.add_argument("--no-cache", action="store_true",
                   help="skip the per-file content-hash findings cache "
                        "(tools/analyzer/.cache.json)")
    p.add_argument("--changed", action="store_true",
                   help="analyze only files git reports as changed, "
                        "plus their reverse dependencies from the "
                        "cross-module import graph")
    args = p.parse_args(argv)

    if args.list_checkers:
        for cid, mod in checker_registry().items():
            doc = (mod.__doc__ or "").strip().splitlines()
            print(f"{cid}\t{doc[0] if doc else ''}")
        return 0

    paths = args.paths or [
        p if os.path.isabs(p) else os.path.join(_REPO, p)
        for p in DEFAULT_PATHS
    ]
    checkers = None
    if args.checkers:
        checkers = [c.strip() for c in args.checkers.split(",") if c.strip()]
    if args.no_baseline:
        baseline = None
    elif args.baseline is not None:
        baseline = args.baseline
    else:
        baseline = "default"

    changed = None
    if args.changed:
        changed = _git_changed_files()
        if changed is None:
            print("warning: --changed needs a git checkout; analyzing "
                  "everything", file=sys.stderr)

    cache = None
    if not args.no_cache and changed is None:
        cache = default_cache_path()

    try:
        result = run_analysis(paths, checkers=checkers, baseline=baseline,
                              cache=cache, changed=changed)
    except ValueError as exc:  # unknown checker ids
        print(f"error: {exc}", file=sys.stderr)
        return 2

    if args.format == "json":
        print(json.dumps(result.to_dict(), indent=2, sort_keys=True))
    elif args.format == "sarif":
        print(render_sarif(result))
    else:
        print(render_text(result))
    if any(f.checker == "usage" for f in result.findings):
        return 2  # misconfigured invocation, not a lint failure
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
