"""Tier-1 gate: ruff (general-purpose lint) is clean, when available.

tpumnist-lint (tools/analyzer) owns the codebase-SPECIFIC invariants;
ruff owns the generic ones (pyflakes/pycodestyle/bugbear, configured in
pyproject.toml ``[tool.ruff]``). The container may not ship ruff — the
gate then skips cleanly rather than failing on a missing dev tool;
``tools/lint.sh`` prints the same skip.
"""

import os
import shutil
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ruff():
    exe = shutil.which("ruff")
    if exe:
        return [exe]
    probe = subprocess.run([sys.executable, "-m", "ruff", "--version"],
                           capture_output=True)
    if probe.returncode == 0:
        return [sys.executable, "-m", "ruff"]
    return None


def test_ruff_check_is_clean():
    runner = _ruff()
    if runner is None:
        pytest.skip("ruff is not installed in this environment")
    proc = subprocess.run(
        runner + ["check", "--no-cache",
                  "pytorch_distributed_mnist_tpu", "tools", "tests"],
        capture_output=True, text=True, cwd=_REPO, timeout=300)
    assert proc.returncode == 0, \
        f"ruff check failed:\n{proc.stdout}\n{proc.stderr}"
