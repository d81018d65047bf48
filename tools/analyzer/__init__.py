"""tpumnist-lint: AST-based invariant checker for the tpu-mnist codebase.

Five invariant families, each encoding an incident or asserted property
from PRs 1-4 (docs/DESIGN.md §8 maps checker -> incident):

- ``collective-symmetry``   collectives never sit under host-conditional
                            control flow (the structural-hang class)
- ``agreement-except-breadth``  exception funnels on agreement paths
                            catch broadly (the zlib.error strand class)
- ``trace-purity``          traced/lowered functions are pure: no host
                            side effects, no tracer concretization
- ``recompile-hazard``      AOT executables get arrays; jit sites
                            declare hashable config static
- ``lock-discipline``       no blocking work under engine/pool/sink
                            locks; one global acquisition order
- ``registry-drift``        fault-point registry == maybe_fault hooks
- ``marker-registry``       pytest markers used == markers registered

Analyzer v2 (PRs 6-19 incident record) adds a project-wide def/call
index (``_ast_util.ProjectIndex``: import + ``self._attr = fn`` factory
resolution, reachability queries), per-file content-hash caching of
findings, a SARIF emitter, and five cross-module checkers:

- ``thread-lifecycle``      every Thread/Timer/Popen join/reap-reachable
                            on all exit paths of its owner
- ``handler-discipline``    every do_GET/do_POST branch replies exactly
                            once; body reads length-bounded
- ``generation-ordering``   installs under a lock re-compare the
                            generation/epoch counter under that lock
- ``short-read``            HTTP body reads verify Content-Length
- ``donated-reuse``         no reads of a donate_argnums argument after
                            the donating call

Run it::

    python -m tools.analyzer [--format text|json] [--baseline FILE] [paths]

or from tests (the tier-1 gate)::

    from tools.analyzer import run_analysis
    result = run_analysis(["pytorch_distributed_mnist_tpu", "tools"])
    assert result.ok, result.findings

Pure stdlib; never imports the analyzed code.
"""

from tools.analyzer.core import (
    SCHEMA_VERSION,
    AnalysisResult,
    CheckerResult,
    Finding,
    Module,
    analyze_snippet,
    checker_registry,
    default_baseline_path,
    default_cache_path,
    load_baseline,
    render_sarif,
    render_text,
    run_analysis,
)

__all__ = [
    "SCHEMA_VERSION",
    "AnalysisResult",
    "CheckerResult",
    "Finding",
    "Module",
    "analyze_snippet",
    "checker_registry",
    "default_baseline_path",
    "default_cache_path",
    "load_baseline",
    "render_sarif",
    "render_text",
    "run_analysis",
]
