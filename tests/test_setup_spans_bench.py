"""Set-up's six per-layer metrics (``startup_s``, ``state_init_s``,
``reference_check_s``, ``trace_s``, ``lower_s``, ``setup_unaccounted_s``)
end to end on the CPU: traced runs of a tiny ViT cell (runner ``train``)
and a tiny token cell (runner ``train_lm_mtp``, which opens ``init`` twice
and ``init_state`` inside its check) in a temporary copy of the benchmark.
The readers read ``CompileLog``'s spans and totals
(``utils/profiling.py``); ``tests/test_compile_cache.py`` has the log's own
tests."""

import contextlib
import io
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tests"))

from bench_helpers import add_cell, make_bench_root, write_spec  # noqa: E402
from test_instella_bench import TINY_CONFIG, TINY_JOB  # noqa: E402

from benchmark import peaks, trace  # noqa: E402
from benchmark import run as harness  # noqa: E402
from pytorch_distributed_mnist_tpu.utils.profiling import (  # noqa: E402
    compile_log,
)

PARTS = ("startup_s", "state_init_s", "reference_check_s", "warm_pass_s",
         "setup_unaccounted_s")
NEW = ("startup_s", "state_init_s", "reference_check_s", "trace_s",
       "lower_s", "setup_unaccounted_s")


@pytest.fixture()
def tiny_root(tmp_path, monkeypatch):
    """A benchmark root with ``tiny_1chip`` and ``tiny_instella``, and, as
    tests/benchmark's ``fixture_trace``, the hand-made trace and a lent
    peak in place of the device plane and peak the CPU has not."""
    root, spec = make_bench_root(tmp_path)
    add_cell(root, spec, name="tiny_instella", config=TINY_CONFIG,
             traffic={"name": "tiny_lm_mtp", **TINY_JOB}, chips=1)
    write_spec(root, spec)
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "hbm_bytes_per_s": 1e11})
    planes = trace.load(os.path.join(
        REPO, "tests", "benchmark", "fixtures", "two_chips.xplane.pb"))
    monkeypatch.setattr(trace, "load", lambda path: planes)
    return root


def traced_cell(root, cell):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        line = harness.run_cell(cell, 2**31 + 11, 0.2, True, root=root,
                                require_platform="cpu", cache_dir="")
    notes = [json.loads(x) for x in out.getvalue().splitlines()]
    return line, next(n for n in notes if n.get("kind") == "setup")


@pytest.mark.parametrize("cell", ["tiny_1chip", "tiny_instella"])
def test_the_parts_of_setup_add_up_to_setup_s(tiny_root, cell):
    line, setup = traced_cell(tiny_root, cell)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(line["metrics"][k]["unit"] == "s" for k in NEW)
    assert all(math.isfinite(got[k]) and got[k] >= 0 for k in NEW)
    assert got["reference_check_s"] > 0 and got["state_init_s"] > 0
    # The partition: from the origin of setup_s to the warming pass's end,
    # every second is in one part. setup_s is taken one stats() later.
    assert sum(got[k] for k in PARTS) == pytest.approx(
        setup["setup_s"], abs=0.2)
    assert got["setup_unaccounted_s"] < 0.5 * setup["setup_s"]
    # What jax did fits into what is left after startup, and tracing, a
    # union over nested events, into the spans that trace anything.
    after_startup = setup["setup_s"] - got["startup_s"]
    assert got["trace_s"] + got["lower_s"] + got["compile_s"] \
        <= after_startup
    assert 0 < got["trace_s"] < (got["warm_pass_s"]
                                 + got["reference_check_s"]
                                 + got["state_init_s"])
    assert got["lower_s"] > 0
    # The note of set-up names the functions that were traced longest.
    assert setup["compile"]["trace_ms"] == pytest.approx(
        got["trace_s"] * 1e3)
    assert setup["programs"]["train_pass"]["functions"]


def test_a_program_that_keeps_no_spans_reports_none_of_them(
        tiny_root, monkeypatch):
    """The parent of PR 35 under these readers: its ``CompileLog`` has the
    backend's totals and nothing else, and the six metrics are left out of
    the line."""
    stats = compile_log.stats

    def as_before():
        old = stats()
        del old["spans"]
        for key in ("trace_ms", "lower_ms", "cache_load_ms"):
            del old["totals"][key]
        return old

    monkeypatch.setattr(compile_log, "stats", as_before)
    line, _setup = traced_cell(tiny_root, "tiny_1chip")
    assert not set(NEW) & set(line["metrics"])
    assert {"compile_s", "cache_misses", "warm_pass_s"} <= set(
        line["metrics"])
