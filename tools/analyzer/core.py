"""tpumnist-lint core: file collection, checker dispatch, baseline, output.

The analyzer is a *codebase-specific* static pass: each checker encodes an
invariant PRs 1-4 established the hard way (docs/DESIGN.md §8 maps each
one to the incident it came from). It is pure stdlib (``ast``) — it never
imports the code under analysis, so it runs in milliseconds with no jax
backend and can gate tier-1.

Baseline contract: ``baseline.json`` is a list of triaged-accepted
findings. Every entry MUST carry a non-empty ``justification`` (an entry
without one is a config error, not a suppression), and every entry must
suppress at least one current finding — a stale entry (the code it
excused is gone) fails the run, so the baseline can only shrink or be
consciously re-justified, never silently rot. Staleness is judged only
when the entry's file is part of the analyzed set (or is gone from disk
entirely): linting a single file must not condemn entries for files the
run never looked at.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 1

#: Version salt for the on-disk findings cache — bump whenever a checker's
#: semantics change in a way file hashes cannot see.
CACHE_VERSION = 1

#: Checker ids whose findings a baseline entry may suppress. Parse errors
#: are never baselinable: an unparseable file means the analyzer saw
#: nothing, which must stay loud.
_UNBASELINABLE = {"parse-error", "usage"}


@dataclasses.dataclass
class Finding:
    """One analyzer hit: where, which invariant, what to do about it."""

    checker: str
    path: str
    line: int
    col: int
    message: str
    hint: str = ""
    symbol: str = ""  # enclosing function/class — stable baseline anchor

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        text = f"{self.path}:{self.line}:{self.col}: {self.checker}{sym}: " \
               f"{self.message}"
        if self.hint:
            text += f"\n    hint: {self.hint}"
        return text


@dataclasses.dataclass
class Module:
    """One parsed source file handed to every checker."""

    path: str  # repo-relative (posix) when a repo root is found
    tree: ast.Module
    source: str
    abspath: str = ""  # "" for in-memory snippets


@dataclasses.dataclass
class CheckerResult:
    findings: List[Finding]
    report: Optional[Dict] = None


@dataclasses.dataclass
class AnalysisResult:
    findings: List[Finding]            # NOT suppressed by the baseline
    suppressed: List[Tuple[Finding, Dict]]
    stale_baseline: List[Dict]         # entries that suppressed nothing
    baseline_problems: List[str]       # malformed entries / unreadable file
    reports: Dict[str, Dict]           # checker id -> structured report
    n_files: int = 0
    checkers: Tuple[str, ...] = ()
    paths: Tuple[str, ...] = ()
    cache_info: Optional[Dict] = None  # {"hit": bool, "files": N}

    @property
    def ok(self) -> bool:
        return not (self.findings or self.stale_baseline
                    or self.baseline_problems)

    def to_dict(self) -> Dict:
        return {
            "schema_version": SCHEMA_VERSION,
            "paths": list(self.paths),
            "checkers": list(self.checkers),
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [
                {**f.to_dict(), "justification": e.get("justification", "")}
                for f, e in self.suppressed
            ],
            "stale_baseline": list(self.stale_baseline),
            "baseline_problems": list(self.baseline_problems),
            "reports": self.reports,
            "cache": self.cache_info,
            "summary": {
                "files": self.n_files,
                "findings": len(self.findings),
                "suppressed": len(self.suppressed),
                "stale_baseline": len(self.stale_baseline),
                "ok": self.ok,
            },
        }


# ---------------------------------------------------------------------------
# File collection
# ---------------------------------------------------------------------------

_SKIP_DIRS = {"__pycache__", ".git", ".xla_cache", "node_modules"}


def collect_files(paths: Sequence[str]) -> Tuple[List[str], List[Finding]]:
    """Expand files/directories into a sorted, de-duplicated .py list."""
    files: List[str] = []
    problems: List[Finding] = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
        elif os.path.isdir(p):
            for dirpath, dirnames, names in os.walk(p):
                dirnames[:] = sorted(
                    d for d in dirnames if d not in _SKIP_DIRS)
                for name in sorted(names):
                    if name.endswith(".py"):
                        files.append(os.path.join(dirpath, name))
        else:
            problems.append(Finding(
                checker="usage", path=p, line=0, col=0,
                message=f"path does not exist: {p!r}"))
    seen, unique = set(), []
    for f in files:
        key = os.path.abspath(f)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return unique, problems


def find_repo_root(start: str,
                   _cache: Optional[Dict[str, Optional[str]]] = None,
                   ) -> Optional[str]:
    """Nearest ancestor holding a pyproject.toml — the path-normalization
    anchor (baseline paths stay stable whatever cwd invoked the tool).

    ``_cache`` (a per-RUN dict keyed by start directory) elides the
    repeated upward isfile walks when many analyzed files share a tree.
    It is never module-global: a run must see the filesystem as it is,
    not as a previous test's tmpdir left it.
    """
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    if _cache is not None and cur in _cache:
        return _cache[cur]
    start_dir = cur
    root: Optional[str] = None
    while True:
        if os.path.isfile(os.path.join(cur, "pyproject.toml")):
            root = cur
            break
        parent = os.path.dirname(cur)
        if parent == cur:
            break
        cur = parent
    if _cache is not None:
        _cache[start_dir] = root
    return root


def _normalize(path: str,
               root_cache: Optional[Dict[str, Optional[str]]] = None) -> str:
    root = find_repo_root(path, root_cache)
    ap = os.path.abspath(path)
    if root and (ap == root or ap.startswith(root + os.sep)):
        return os.path.relpath(ap, root).replace(os.sep, "/")
    return path.replace(os.sep, "/")


def parse_modules(files: Sequence[str]) \
        -> Tuple[List[Module], List[Finding]]:
    modules: List[Module] = []
    problems: List[Finding] = []
    root_cache: Dict[str, Optional[str]] = {}
    for path in files:
        norm = _normalize(path, root_cache)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=path)
        except (OSError, SyntaxError, ValueError) as exc:
            problems.append(Finding(
                checker="parse-error", path=norm,
                line=getattr(exc, "lineno", 0) or 0, col=0,
                message=f"could not parse: {exc}"))
            continue
        modules.append(Module(path=norm, tree=tree, source=source,
                              abspath=os.path.abspath(path)))
    return modules, problems


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

_ENTRY_KEYS = {"checker", "path", "contains", "justification"}


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str]) -> Tuple[List[Dict], List[str]]:
    """Read + validate a baseline file; returns ``(entries, problems)``.
    A missing default baseline is an empty baseline; a missing *explicit*
    baseline is a problem."""
    if path is None:
        return [], []
    if not os.path.isfile(path):
        return [], [f"baseline file not found: {path!r}"]
    try:
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
    except (OSError, ValueError) as exc:
        return [], [f"baseline {path!r} unreadable: {exc}"]
    if not isinstance(raw, list):
        return [], [f"baseline {path!r} must be a JSON list of entries"]
    entries, problems = [], []
    for i, entry in enumerate(raw):
        if not isinstance(entry, dict) or \
                not _ENTRY_KEYS.issubset(entry.keys()):
            problems.append(
                f"baseline entry #{i} must be an object with keys "
                f"{sorted(_ENTRY_KEYS)}: got {entry!r}")
            continue
        if not str(entry.get("justification", "")).strip():
            problems.append(
                f"baseline entry #{i} ({entry.get('checker')} @ "
                f"{entry.get('path')}) has no justification — every "
                f"accepted finding must say WHY it is acceptable")
            continue
        if entry.get("checker") in _UNBASELINABLE:
            problems.append(
                f"baseline entry #{i}: {entry.get('checker')!r} findings "
                f"cannot be baselined")
            continue
        entries.append(entry)
    return entries, problems


def _entry_matches(entry: Dict, finding: Finding) -> bool:
    if entry["checker"] != finding.checker:
        return False
    if entry["path"] != finding.path:
        return False
    needle = str(entry["contains"])
    return needle in finding.message or needle == finding.symbol


def apply_baseline(findings: List[Finding], entries: List[Dict],
                   analyzed_paths: Optional[Sequence[str]] = None,
                   file_exists=None):
    """Split findings into (kept, suppressed) and report stale entries.

    An unused entry is stale only when this run actually judged it: its
    file was part of ``analyzed_paths`` (normalized; ``None`` means
    everything was), or ``file_exists`` says the file is gone entirely
    (a deleted file never re-enters the analyzed set, and its entries
    must not rot silently). Linting a path subset must not condemn
    entries for files the run never looked at.
    """
    kept: List[Finding] = []
    suppressed: List[Tuple[Finding, Dict]] = []
    used = [False] * len(entries)
    for f in findings:
        hit = None
        if f.checker not in _UNBASELINABLE:
            for i, entry in enumerate(entries):
                if _entry_matches(entry, f):
                    hit = (i, entry)
                    break
        if hit is None:
            kept.append(f)
        else:
            used[hit[0]] = True
            suppressed.append((f, hit[1]))
    judged = set(analyzed_paths) if analyzed_paths is not None else None
    stale = [entry for i, entry in enumerate(entries)
             if not used[i]
             and (judged is None or entry.get("path") in judged
                  or (file_exists is not None
                      and not file_exists(str(entry.get("path")))))]
    return kept, suppressed, stale


# ---------------------------------------------------------------------------
# Running
# ---------------------------------------------------------------------------


def checker_registry() -> Dict[str, object]:
    """Ordered ``{checker_id: module}``; import deferred so ``core`` has
    no import cycle with the checker package."""
    from tools.analyzer import checkers

    return checkers.REGISTRY


def default_cache_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".cache.json")


def _file_hashes(files: Sequence[str]) -> Dict[str, str]:
    """Repo-relative path -> sha256 of file bytes (unreadable files hash
    to "" so the cache can never mask a parse-error finding)."""
    hashes: Dict[str, str] = {}
    root_cache: Dict[str, Optional[str]] = {}
    for path in files:
        norm = _normalize(path, root_cache)
        try:
            with open(path, "rb") as f:
                hashes[norm] = hashlib.sha256(f.read()).hexdigest()
        except OSError:
            hashes[norm] = ""
    return hashes


def _load_cache(path: str, ids: Sequence[str], hashes: Dict[str, str],
                paths: Sequence[str]) -> Optional[Dict]:
    """The cached payload when it is valid for exactly this run: same
    cache schema, same checker list, same input paths, same file set
    with byte-identical contents. Anything else is a miss."""
    try:
        with open(path, encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict):
        return None
    if payload.get("cache_version") != CACHE_VERSION:
        return None
    if payload.get("schema_version") != SCHEMA_VERSION:
        return None
    if payload.get("checkers") != list(ids):
        return None
    if payload.get("paths") != [str(p) for p in paths]:
        return None
    if payload.get("files") != hashes:
        return None
    return payload


def _store_cache(path: str, ids: Sequence[str], hashes: Dict[str, str],
                 paths: Sequence[str], findings: Sequence[Finding],
                 reports: Dict[str, Dict],
                 module_paths: Sequence[str]) -> None:
    payload = {
        "cache_version": CACHE_VERSION,
        "schema_version": SCHEMA_VERSION,
        "checkers": list(ids),
        "paths": [str(p) for p in paths],
        "files": hashes,
        "findings": [f.to_dict() for f in findings],
        "reports": reports,
        "module_paths": list(module_paths),
    }
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError:
        pass  # best effort: a cold run next time, never a failure now
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def run_analysis(
    paths: Sequence[str],
    checkers: Optional[Sequence[str]] = None,
    baseline: Optional[str] = "default",
    cache: Optional[str] = None,
    changed: Optional[Sequence[str]] = None,
) -> AnalysisResult:
    """Analyze ``paths`` with the selected checkers.

    ``baseline``: a file path, ``"default"`` (the checked-in
    ``tools/analyzer/baseline.json``), or ``None`` (no suppression).

    ``cache``: a file path for the per-file content-hash findings cache.
    A warm run on an unchanged tree (same files, same bytes, same
    checkers) skips parsing and checking entirely and replays the stored
    findings byte-for-byte; the baseline is always re-applied fresh so
    editing it never needs a cache flush.

    ``changed``: restrict *checking* to these files plus every module
    that transitively imports one of them (reverse dependencies from the
    cross-module index). The whole tree is still parsed and indexed —
    cross-module checkers must see the full call graph — but findings
    are only produced for the restricted set, and baseline staleness is
    only judged there (the existing path-subset contract).
    """
    registry = checker_registry()
    ids = list(checkers) if checkers is not None else list(registry)
    unknown = [c for c in ids if c not in registry]
    if unknown:
        raise ValueError(
            f"unknown checker(s) {unknown}; available: {list(registry)}")

    files, problems = collect_files(paths)
    cache_info: Optional[Dict] = None
    hashes: Optional[Dict[str, str]] = None
    payload: Optional[Dict] = None
    if cache is not None and changed is None:
        hashes = _file_hashes(files)
        payload = _load_cache(cache, ids, hashes, paths)
        cache_info = {"hit": payload is not None, "files": len(files)}

    if payload is not None:
        findings = list(problems) + \
            [Finding(**d) for d in payload["findings"]]
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.checker))
        reports = dict(payload["reports"])
        module_paths = list(payload["module_paths"])
    else:
        modules, parse_problems = parse_modules(files)
        findings = list(problems) + list(parse_problems)
        needs_index = changed is not None or any(
            getattr(registry[cid], "NEEDS_INDEX", False) for cid in ids)
        index = None
        if needs_index:
            from tools.analyzer._ast_util import ProjectIndex

            index = ProjectIndex(modules)
        target_modules = modules
        if changed is not None:
            root_cache: Dict[str, Optional[str]] = {}
            norm_changed = {_normalize(p, root_cache) for p in changed}
            restrict = index.reverse_dependencies(
                {m.path for m in modules if m.path in norm_changed})
            target_modules = [m for m in modules if m.path in restrict]
        reports = {}
        for cid in ids:
            mod = registry[cid]
            if getattr(mod, "NEEDS_INDEX", False):
                result: CheckerResult = mod.run(target_modules, index)
            else:
                result = mod.run(target_modules)
            findings.extend(result.findings)
            if result.report is not None:
                reports[cid] = result.report
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.checker))
        module_paths = [m.path for m in target_modules]
        if cache is not None and changed is None:
            # usage findings (bad input paths) are re-derived fresh each
            # run; everything content-derived is cached.
            _store_cache(cache, ids, hashes, paths,
                         [f for f in findings if f.checker != "usage"],
                         reports, module_paths)

    if baseline == "default":
        bl_path: Optional[str] = default_baseline_path()
        if not os.path.isfile(bl_path):
            bl_path = None
    else:
        bl_path = baseline
    entries, bl_problems = load_baseline(bl_path)

    def _entry_file_exists(path: str) -> bool:
        # Entry paths are repo-relative (or whatever the run passed in):
        # resolve against cwd, then against the baseline file's repo.
        if os.path.isfile(path):
            return True
        root = find_repo_root(bl_path) if bl_path else None
        return bool(root) and os.path.isfile(os.path.join(root, path))

    kept, suppressed, stale = apply_baseline(
        findings, entries, analyzed_paths=module_paths,
        file_exists=_entry_file_exists)

    return AnalysisResult(
        findings=kept, suppressed=suppressed, stale_baseline=stale,
        baseline_problems=bl_problems, reports=reports,
        n_files=len(module_paths), checkers=tuple(ids),
        paths=tuple(paths), cache_info=cache_info,
    )


def analyze_snippet(
    source: str,
    checkers: Optional[Sequence[str]] = None,
    filename: str = "snippet.py",
) -> List[Finding]:
    """Run checkers over one in-memory source string (the fixture-test
    entry point). No baseline, no filesystem."""
    registry = checker_registry()
    ids = list(checkers) if checkers is not None else list(registry)
    unknown = [c for c in ids if c not in registry]
    if unknown:
        raise ValueError(
            f"unknown checker(s) {unknown}; available: {list(registry)}")
    tree = ast.parse(source, filename=filename)
    module = Module(path=filename, tree=tree, source=source)
    index = None
    if any(getattr(registry[cid], "NEEDS_INDEX", False) for cid in ids):
        from tools.analyzer._ast_util import ProjectIndex

        index = ProjectIndex([module])
    findings: List[Finding] = []
    for cid in ids:
        mod = registry[cid]
        if getattr(mod, "NEEDS_INDEX", False):
            findings.extend(mod.run([module], index).findings)
        else:
            findings.extend(mod.run([module]).findings)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.checker))
    return findings


def render_text(result: AnalysisResult) -> str:
    lines: List[str] = []
    for f in result.findings:
        lines.append(f.render())
    for entry in result.stale_baseline:
        lines.append(
            f"stale baseline entry: {entry['checker']} @ {entry['path']} "
            f"(contains {entry['contains']!r}) no longer matches anything "
            f"— delete it (the code it excused is gone)")
    for problem in result.baseline_problems:
        lines.append(f"baseline problem: {problem}")
    s = result.to_dict()["summary"]
    cache_note = ""
    if result.cache_info is not None:
        cache_note = " [cache hit]" if result.cache_info.get("hit") \
            else " [cache miss]"
    lines.append(
        f"tpumnist-lint: {s['files']} files, {s['findings']} finding(s), "
        f"{s['suppressed']} baselined, {s['stale_baseline']} stale "
        f"baseline entr{'y' if s['stale_baseline'] == 1 else 'ies'} -> "
        f"{'OK' if result.ok else 'FAIL'}{cache_note}")
    return "\n".join(lines)


def render_sarif(result: AnalysisResult) -> str:
    """Minimal valid SARIF 2.1.0: one run, one rule per checker, one
    result per finding; baselined findings appear with an external
    suppression carrying the baseline justification."""
    registry = checker_registry()
    rule_ids = sorted({*result.checkers,
                       *(f.checker for f in result.findings),
                       *(f.checker for f, _ in result.suppressed)})
    rules = []
    for cid in rule_ids:
        mod = registry.get(cid)
        doc = (getattr(mod, "__doc__", "") or "").strip().splitlines()
        rules.append({
            "id": cid,
            "shortDescription": {"text": doc[0] if doc else cid},
        })

    def _sarif_result(f: Finding, entry: Optional[Dict] = None) -> Dict:
        text = f.message
        if f.hint:
            text += f" (hint: {f.hint})"
        r: Dict = {
            "ruleId": f.checker,
            "level": "error",
            "message": {"text": text},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(1, f.line),
                               "startColumn": max(1, f.col + 1)},
                },
            }],
        }
        if entry is not None:
            r["suppressions"] = [{
                "kind": "external",
                "justification": str(entry.get("justification", "")),
            }]
        return r

    sarif = {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "tpumnist-lint",
                "version": f"{SCHEMA_VERSION}.0.0",
                "rules": rules,
            }},
            "results": [_sarif_result(f) for f in result.findings]
            + [_sarif_result(f, e) for f, e in result.suppressed],
        }],
    }
    return json.dumps(sarif, indent=2, sort_keys=True)
