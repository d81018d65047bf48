#!/usr/bin/env bash
# One-shot lint runner: tpumnist-lint analyzer + ruff + the tier-1 lint
# gate tests. Mirrors exactly what CI enforces:
#
#   tools/lint.sh            # all three stages
#   tools/lint.sh --fast     # analyzer only (the gate itself; the
#                            # warm findings cache makes re-runs
#                            # near-instant)
#   tools/lint.sh --changed  # analyzer only, scoped to git-changed
#                            # files PLUS their reverse dependencies
#                            # from the cross-module import graph
#
# Exit code: first failing stage's code. Ruff is optional tooling — a
# missing binary prints a SKIP (the pytest gate skips the same way).
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"

fail=0
# Record the FIRST failing stage's code (later stages still run, but must
# not overwrite it — the analyzer's 1-vs-2 exit contract survives).
note() { if [ "$fail" -eq 0 ]; then fail=$1; fi; }

analyzer_flags=()
if [ "${1:-}" = "--changed" ]; then
  analyzer_flags+=(--changed)
fi

echo "== tpumnist-lint (tools/analyzer) =="
python -m tools.analyzer "${analyzer_flags[@]+"${analyzer_flags[@]}"}" \
  pytorch_distributed_mnist_tpu tools \
  || note $?

if [ "${1:-}" = "--fast" ] || [ "${1:-}" = "--changed" ]; then
  exit "$fail"
fi

if command -v ruff >/dev/null 2>&1; then
  echo "== ruff check =="
  ruff check --no-cache pytorch_distributed_mnist_tpu tools tests \
    || note $?
else
  echo "== ruff check: SKIP (ruff not installed) =="
fi

echo "== tier-1 lint gate (pytest -m lint) =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m lint \
  -p no:cacheprovider || note $?

exit "$fail"
