"""The documents describe the tree that is there.

``README.md`` is what a user reads before running anything and ``PERF.md``
what every later session plans from; both drifted for rounds because
nothing read them (a Layout row for a deleted tool, six command lines of a
retired benchmark). These cases read them: a path they name exists, a
command line they show is one its parser accepts.
"""

import argparse
import importlib.util
import os
import re
import shlex
from unittest import mock

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PACKAGE = "pytorch_distributed_mnist_tpu"
_SOURCE_SUFFIXES = (".py", ".md", ".json", ".sh", ".cpp", ".toml")


def _read(name):
    with open(os.path.join(_REPO, name), encoding="utf-8") as f:
        return f.read()


def _fenced(text):
    return re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M)


def _inline(text):
    return re.findall(r"`([^`]+)`",
                      re.sub(r"^```.*?^```", "", text, flags=re.S | re.M))


def _named_paths(text):
    """The words inside the code spans and fenced blocks of ``text`` that
    name a source file or a directory: plain characters only (no
    ``<cell>``, no glob), a source suffix or a trailing slash, neither
    absolute nor hidden nor an option."""
    found = set()
    for span in _inline(text) + _fenced(text):
        for word in re.split(r"[\s,;:()\[\]\"'=|]+", span):
            word = word.rstrip(".")
            if (re.fullmatch(r"[\w./-]+", word)
                    and word.endswith(_SOURCE_SUFFIXES + ("/",))
                    and not word.startswith(("/", "-"))
                    and not os.path.basename(word.rstrip("/")).startswith(".")):
                found.add(word)
    return found


def _basenames():
    """Every file name of the checkout, less what .gitignore keeps out of
    a commit (build/ holds copies of parent commits)."""
    names = set()
    for _, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("build", "dist", "chiprun_out")]
        names.update(files)
    return names


def _missing(paths):
    """Those of ``paths`` the checkout does not hold. A path is relative to
    the checkout or to the package (the documents write ``serve/router.py``
    for ``pytorch_distributed_mnist_tpu/serve/router.py``); a bare file
    name (``canary.py`` in the row of ``serve/``) has to be some file's
    name. Passed over: what a run writes (``checkpoints/``,
    ``chiprun_out/``) and words whose first part is no entry of the
    checkout or the package (``normalize/cast/`` is prose)."""
    roots = (_REPO, os.path.join(_REPO, _PACKAGE))
    entries = {e for root in roots for e in os.listdir(root)}
    names = _basenames()
    missing = []
    for path in sorted(paths):
        first, _, rest = path.partition("/")
        if first in ("checkpoints", "chiprun_out"):
            continue
        if not rest and not path.endswith("/"):
            ok = path in names
        elif rest and first not in entries:
            continue
        else:
            ok = any(os.path.exists(os.path.join(root, path))
                     for root in roots)
        if not ok:
            missing.append(path)
    return missing


def test_readme_names_only_paths_that_exist():
    paths = _named_paths(_read("README.md"))
    assert len(paths) >= 50, sorted(paths)
    assert not _missing(paths)


def test_readme_layout_rows_name_existing_entries():
    text = _read("README.md")
    table = text[text.index("## Layout"):text.index("### Model zoo")]
    rows = re.findall(r"^\| `([^`]+)` \|", table, re.M)
    assert len(rows) >= 10, rows
    assert not [r for r in rows
                if not os.path.exists(os.path.join(_REPO, r))]
    for entry in ("benchmark/", "chip_smoke.py"):
        assert entry in rows


def test_perf_layers_and_cells_name_existing_modules():
    """Sections 3 (Layers) and 4 (Cells) of PERF.md: each module path."""
    text = _read("PERF.md")
    part = text[text.index("## 3. Layers"):text.index("## 5. ")]
    paths = _named_paths(part)
    assert len(paths) >= 20, sorted(paths)
    assert not _missing(paths)


# -- command lines ----------------------------------------------------------


def _caught_parser(entry):
    """The ``ArgumentParser`` that ``entry(argv)`` builds, caught at the
    moment it would parse (the tools build theirs inside ``main``)."""
    class Caught(Exception):
        pass

    def grab(self, *args, **kwargs):
        raise Caught(self)

    with mock.patch.object(argparse.ArgumentParser, "parse_args", grab):
        try:
            entry([])
        except Caught as caught:
            return caught.args[0]
    raise AssertionError(f"{entry} parsed nothing")


def _script_main(relative):
    spec = importlib.util.spec_from_file_location(
        "_doc_" + os.path.basename(relative)[:-3],
        os.path.join(_REPO, relative))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.main


def _package_parser(module):
    return importlib.import_module(f"{_PACKAGE}.{module}").build_parser()


# command -> (how a command line of it starts, its parser). A ``tpu-mnist``
# line that goes on with ``serve`` or ``route`` is that subcommand's.
_COMMANDS = {
    "tpu-mnist": (("tpu-mnist", f"python -m {_PACKAGE}"),
                  lambda: _package_parser("cli")),
    "tpu-mnist serve": (("tpu-mnist serve", f"python -m {_PACKAGE} serve"),
                        lambda: _package_parser("serve.server")),
    "tpu-mnist route": (("tpu-mnist route", f"python -m {_PACKAGE} route"),
                        lambda: _package_parser("serve.router")),
    "tools/loadgen.py": (("python tools/loadgen.py", "tools/loadgen.py"),
                         lambda: _caught_parser(
                             _script_main("tools/loadgen.py"))),
    "tools/chaos.py": (("python tools/chaos.py", "tools/chaos.py"),
                       lambda: _caught_parser(
                           _script_main("tools/chaos.py"))),
    "benchmark/run.py": (("python3 benchmark/run.py",
                          "python benchmark/run.py", "benchmark/run.py"),
                         lambda: _caught_parser(
                             _script_main("benchmark/run.py"))),
}


def _command_lines(text):
    """Shell words of every command the README shows: the lines of its
    fenced blocks with continuations joined and comments dropped, and the
    inline spans."""
    lines = []
    for block in _fenced(text):
        lines += re.sub(r"\\\n", " ", block).splitlines()
    lines += [re.sub(r"\s*\n\s*", " ", span) for span in _inline(text)]
    for line in lines:
        try:
            words = shlex.split(line, comments=True)
        except ValueError:  # an unbalanced quote: prose, not a command
            continue
        if words:
            yield words


def _flags_of(command, text):
    """The ``--options`` the README gives ``command``, each with the line
    it stands in. A ``tools/chaos.py`` line hands what follows a bare
    ``--`` to the trainer: those belong to ``tpu-mnist``."""
    starts = {c: tuple(tuple(s.split()) for s in heads)
              for c, (heads, _) in _COMMANDS.items()}
    flags = {}
    for words in _command_lines(text):
        if words[0] == "chiprun" and "--" in words:
            words = words[words.index("--") + 1:]  # the chip tool's command
        while words and re.fullmatch(r"[A-Z_]+=.*", words[0]):
            words = words[1:]  # VAR=value before the command
        owner = max((c for c, heads in starts.items() for h in heads
                     if tuple(words[:len(h)]) == h),
                    key=len, default=None)
        rest = words
        if owner == "tools/chaos.py" and "--" in words:
            cut = words.index("--")
            rest, handed = words[:cut], words[cut + 1:]
            if command == "tpu-mnist":
                owner, rest = "tpu-mnist", handed
        if owner != command:
            continue
        for word in rest:
            if re.fullmatch(r"--[a-z][\w-]*(=.*)?", word):
                flags.setdefault(word.split("=")[0], " ".join(words))
    return flags


@pytest.mark.parametrize("command", sorted(_COMMANDS))
def test_readme_command_lines_use_options_the_parser_accepts(command):
    flags = _flags_of(command, _read("README.md"))
    assert flags, f"README shows no {command} command line with an option"
    known = _COMMANDS[command][1]()._option_string_actions
    unknown = {f: line for f, line in flags.items() if f not in known}
    assert not unknown, unknown
