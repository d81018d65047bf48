"""``benchmark/`` is the only place in the repository that measures speed.

PR 29 retired ``bench.py``, ``tools/bench_kernels.py``,
``tools/sweep_flash.py`` and ``tools/northstar.py`` with the 37 ``BENCH_*``
environment variables they read (sizes, fake peaks, injected failures, a
CPU schema switch). These two cases keep them gone: a second benchmark, or
a switch that makes a measurement say something else on request, comes
back through an import or through such a variable.
"""

import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RETIRED_MODULES = ("bench", "bench_kernels", "sweep_flash", "northstar")
_IMPORT = re.compile(
    r"^\s*(?:import|from)\s+(?:tools\.)?(%s)\b" % "|".join(_RETIRED_MODULES),
    re.MULTILINE)
_VARIABLE = re.compile(r"BENCH_[A-Z_]+")


# What .gitignore keeps out of a commit, as far as it holds source files:
# build/ has the copies of parent commits that earlier PRs measured against.
_NOT_COMMITTED = {"build", "dist", "chiprun_out", "__pycache__"}


def _tracked(*suffixes):
    """(path, text) of every file of the checkout whose name ends in one of
    ``suffixes``. Walks the tree (the driver's checkout need not be a git
    repository) and passes over what .gitignore names and hidden
    directories."""
    for top, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if d not in _NOT_COMMITTED
                   and not d.startswith(".") and not d.endswith(".egg-info")]
        for name in files:
            if name.endswith(suffixes):
                full = os.path.join(top, name)
                with open(full, encoding="utf-8") as f:
                    yield os.path.relpath(full, _REPO), f.read()


def test_no_file_imports_a_retired_benchmark():
    assert any(path == "chip_smoke.py" for path, _ in _tracked(".py"))
    hits = [f"{path}: {m.group(0).strip()}"
            for path, text in _tracked(".py")
            for m in _IMPORT.finditer(text)]
    assert not hits, hits


def test_no_bench_environment_variable_outside_the_benchmark():
    """``benchmark/run.py`` keeps ``_BENCH_DIR`` and ``BENCH_DIRNAME``,
    which are names in its own code and read nothing from the
    environment; this file holds the pattern itself."""
    own = os.path.relpath(__file__, _REPO)
    hits = [f"{path}: {m.group(0)}"
            for path, text in _tracked(".py", ".sh", ".toml")
            if not path.startswith("benchmark/") and path != own
            for m in _VARIABLE.finditer(text)]
    assert not hits, hits
