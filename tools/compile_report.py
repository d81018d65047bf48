"""Print the per-program compile_stats from run artifacts.

The compile-latency subsystem (utils/compile_cache.py + the trainer's AOT
precompile) records, for every program, its compile wall ms, the part of
it that was tracing, lowering and loading from the persistent cache, how
many real XLA backend compiles ran, and whether the cache served it. That
lands in the ``run_summary`` row of a training run's ``--metrics-file``
(``compile_stats`` block).

This tool renders those blocks as a cold-vs-warm table.

Usage:
  python tools/compile_report.py FILE...    # artifact file(s), JSON lines

Exit status: 0 if at least one compile_stats block was found, else 1.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The parts of a program's compile wall that CompileLog keeps apart, and
# the column each gets.
SPLIT = {"trace_ms": "trace", "lower_ms": "lower", "cache_load_ms": "load"}


def _load_lines(path: str):
    """Every JSON object found in ``path`` (one per line; tolerant of
    non-JSON lines and trailing garbage — artifacts are append-style)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(obj, dict):
                    out.append(obj)
    except OSError:
        return []
    return out


def _find_stats(obj: dict):
    """The compile_stats block of an artifact line, or None."""
    stats = obj.get("compile_stats")
    if isinstance(stats, dict) and isinstance(stats.get("programs"), dict):
        return stats
    return None


def report(paths) -> int:
    found = 0
    for path in paths:
        for obj in _load_lines(path):
            stats = _find_stats(obj)
            if stats is None:
                continue
            found += 1
            label = obj.get("kind") or "run"
            print(f"\n{os.path.relpath(path, REPO)} — {label} "
                  f"[{obj.get('platform', '?')}]")
            # Summaries from before CompileLog kept trace, lower and
            # cache-load seconds render without those columns.
            split = [k for k in SPLIT
                     if any(k in rec for rec in stats["programs"].values())]
            print(f"  {'program':<24} {'compile ms':>10}"
                  + "".join(f" {SPLIT[k]:>8}" for k in split)
                  + f" {'XLA':>4} {'cache':>6}")
            for name, rec in sorted(stats["programs"].items()):
                hit = rec.get("persistent_cache_hit")
                cache = ("off" if hit is None else
                         "hit" if hit else "miss")
                print(f"  {name:<24} {rec.get('wall_ms', 0):>10.0f}"
                      + "".join(f" {rec.get(k, 0):>8.0f}" for k in split)
                      + f" {rec.get('backend_compiles', 0):>4} {cache:>6}")
            totals = stats.get("totals", {})
            print(f"  totals: {totals.get('backend_compiles', 0)} XLA "
                  f"compile(s), {totals.get('backend_compile_ms', 0):.0f} ms "
                  f"backend, {totals.get('cache_hits', 0)} hit / "
                  f"{totals.get('cache_misses', 0)} miss"
                  + "".join(f", {SPLIT[k]} {totals[k]:.0f} ms"
                            for k in split if k in totals))
    if not found:
        print("no compile_stats blocks found (artifacts predate the "
              "compile-latency subsystem, or the runs never compiled)",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    paths = list(sys.argv[1:] if argv is None else argv)
    if not paths:
        print(__doc__, file=sys.stderr)
        return 1
    return report(paths)


if __name__ == "__main__":
    sys.exit(main())
