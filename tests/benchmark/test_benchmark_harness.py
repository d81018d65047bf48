"""The harness end to end at a tiny preset on the CPU, its contract, and
that a later PR extends it with files alone.

The runners are called as Python functions with the device requirement
passed in (``require_platform="cpu"``); the command line has no such switch
and is shown to refuse the CPU.
"""

import contextlib
import hashlib
import io
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import peaks, trace
from benchmark import run as harness

from bench_helpers import REPO, TINY_CONFIG, TINY_JOB, add_cell, write_spec

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SECONDS = 0.2


def run_cell(root, cell, *, seed=3, traced=False):
    """(final line, earlier lines) of one run on the CPU, cache off."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        line = harness.run_cell(cell, seed, SECONDS, traced, root=root,
                                require_platform="cpu", cache_dir="")
    return line, [json.loads(x) for x in out.getvalue().splitlines()]


def notes_of(notes, kind):
    return next(n for n in notes if n.get("kind") == kind)


@pytest.fixture()
def fixture_trace(monkeypatch):
    """A CPU trace has no device plane, so the traced path reads the
    hand-made two-chip trace in place of the file the profiler wrote; and
    the table of peaks knows no CPU (an unknown device is an error), so
    the test lends it one."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12})
    planes = trace.load(os.path.join(FIXTURES, "two_chips.xplane.pb"))
    seen = []

    def load(path):
        seen.append(path)
        return planes

    monkeypatch.setattr(trace, "load", load)
    return seen


# -- the train runner ---------------------------------------------------------

def test_one_chip_run_prints_the_contracts_line(bench_root):
    root, _spec = bench_root
    line, notes = run_cell(root, "tiny_1chip")
    assert tuple(line) == harness.RESULT_KEYS
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 3 * 2
    assert set(line["metrics"]) == {"train_images_per_s_per_chip", "setup_s"}
    assert line["metrics"]["train_images_per_s_per_chip"] == {
        "value": pytest.approx(
            line["metrics"]["train_images_per_s_per_chip"]["value"]),
        "unit": "images/s/chip"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    json.dumps(line)
    check = notes_of(notes, "reference_check")
    assert check["ok"] and set(check["errors"]) == set(check["limits"])
    assert any(k.startswith("grad:params/block0/") for k in check["errors"])
    setup = notes_of(notes, "setup")
    assert setup["compiles_in_window"] == 0
    assert setup["interpreted_pallas"] == 0
    passes = notes_of(notes, "passes")
    assert passes["n"] == len(passes["pass_losses"]) >= 3
    # The same seed gives the same weights and inputs: the same losses.
    _line2, notes2 = run_cell(root, "tiny_1chip")
    again = notes_of(notes2, "passes")
    assert again["warm_loss"] == passes["warm_loss"]
    assert again["pass_losses"][:3] == passes["pass_losses"][:3]
    _line3, notes3 = run_cell(root, "tiny_1chip", seed=4)
    assert notes_of(notes3, "passes")["warm_loss"] != passes["warm_loss"]


def test_four_chip_traced_run_reports_the_layers(bench_root, fixture_trace):
    root, spec = bench_root
    line, notes = run_cell(root, "tiny_dp4", traced=True)
    assert tuple(line) == harness.RESULT_KEYS + ("breakdown",)
    assert line["correct"] is True
    assert line["device"]["count"] == 4
    assert line["device"]["busy_s"] == pytest.approx(875e-9)
    assert line["device"]["window_s"] == pytest.approx(1100e-9)
    assert len(fixture_trace) == 1 and fixture_trace[0].endswith(".xplane.pb")
    # Every per-layer metric of the cell whose reader found something, with
    # BENCHMARK.json's unit; peak memory is not reported by the CPU.
    units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    assert set(line["metrics"]) == set(units) - {"peak_hbm_gb"}
    assert all(line["metrics"][k]["unit"] == units[k]
               for k in line["metrics"])
    assert "setup_s" not in line["metrics"]
    assert line["metrics"]["collective_exposed_share"]["value"] == \
        pytest.approx(100 * 180 / 1100)
    steps = 2 * 2  # two traced passes of two steps
    assert line["metrics"]["collective_ms_per_step"]["value"] == \
        pytest.approx(275e-6 / steps)
    assert line["metrics"]["cache_misses"]["value"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["breakdown"]["device_ops"][0][0] == \
        "fusion[kOutput] bf16[32,196,1024]"
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert os.path.isfile(os.path.join(
        root, "chiprun_out", "benchmark", "tiny_dp4.trace.json"))
    assert notes_of(notes, "trace")["devices"] == 2


def test_one_chip_cell_leaves_out_the_parallel_layer(bench_root,
                                                     fixture_trace):
    root, _spec = bench_root
    line, _notes = run_cell(root, "tiny_1chip", traced=True)
    assert "collective_ms_per_step" not in line["metrics"]
    assert {"step_ms", "mfu", "busy_flops_util", "compile_s",
            "input_wait_share"} <= set(line["metrics"])


def test_a_lower_precision_than_the_file_states_is_not_correct(bench_root):
    """The configuration says f32 and is held to f32's tolerance; a build
    hook (``configs/<name>.py``) that computes in bfloat16 fails it."""
    root, spec = bench_root
    config = {**TINY_CONFIG, "name": "tiny-vit-f32", "dtype": "f32"}
    add_cell(root, spec, name="tiny_f32", config=config,
             traffic={"name": "tiny_scan2", **TINY_JOB}, chips=1)
    write_spec(root, spec)
    line, notes = run_cell(root, "tiny_f32")
    assert line["correct"] is True
    assert notes_of(notes, "reference_check")["errors"]["logits"] < 1e-4
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-vit-f32.py"), "w") as f:
        f.write("import jax.numpy as jnp\n"
                "from pytorch_distributed_mnist_tpu.models import get_model\n"
                "def build(run):\n"
                "    return get_model(run.config['model'], "
                "compute_dtype=jnp.bfloat16, **run.config['kwargs'])\n")
    line, notes = run_cell(root, "tiny_f32")
    assert line["correct"] is False
    assert not notes_of(notes, "reference_check")["ok"]


# -- driven by data -----------------------------------------------------------

def _digest(root):
    """Hashes of every file of the harness below ``root``."""
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(base, name)
            if "__pycache__" in path:
                continue
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(bench_root,
                                                             fixture_trace):
    root, spec = bench_root
    before = _digest(REPO)
    # A new configuration, a new traffic mix, a new kind of job and a new
    # per-layer metric: four new files and their BENCHMARK.json entries.
    config = {**TINY_CONFIG, "name": "tinier-vit",
              "kwargs": {**TINY_CONFIG["kwargs"], "depth": 1}}
    add_cell(root, spec, name="tinier_1chip", config=config,
             traffic={"name": "tinier_scan", **TINY_JOB,
                      "steps_per_pass": 3}, chips=1)
    add_cell(root, spec, name="tinier_echo", config=config,
             traffic={"name": "echo_mix", "runner": "echo", "answer": 7.5},
             chips=1)
    with open(os.path.join(root, "benchmark", "runners", "echo.py"),
              "w") as f:
        f.write("def run(run):\n"
                "    run.counters['answer'] = run.traffic['answer']\n"
                "    return {'correct': True, 'attempted': 1, 'failed': 0,\n"
                "            'end_to_end': {'setup_s': 1.0,\n"
                "                           'echo_per_s': 2.0},\n"
                "            'devices': run.devices()}\n")
    with open(os.path.join(root, "benchmark", "layers", "answer.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return run.counters.get('answer')\n")
    spec["end_to_end"].append(
        {"name": "echo_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["tinier_echo"]})
    spec["per_layer"].append(
        {"name": "answer", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Entry and compile",
         "moves": "echo_per_s"})
    write_spec(root, spec)

    line, _ = run_cell(root, "tinier_echo")
    assert line["metrics"] == {
        "setup_s": {"value": 1.0, "unit": "s"},
        "echo_per_s": {"value": 2.0, "unit": "1/s"}}
    line, _ = run_cell(root, "tinier_echo", traced=True)
    # Only the metrics that move something this cell reports, and only
    # where the reader found something to read.
    assert line["metrics"] == {"answer": {"value": 7.5, "unit": "count"}}
    assert "breakdown" not in line

    line, notes = run_cell(root, "tinier_1chip", traced=True)
    assert line["correct"] is True
    assert line["attempted"] % 3 == 0
    assert "answer" not in line["metrics"]  # moves a metric not reported here
    assert "step_ms" in line["metrics"]
    assert any(k == "grad:params/block0/attn/qkv/kernel"
               for k in notes_of(notes, "reference_check")["errors"])

    added = {"benchmark/configs/tinier-vit.json",
             "benchmark/traffic/tinier_scan.json",
             "benchmark/traffic/echo_mix.json",
             "benchmark/runners/echo.py", "benchmark/layers/answer.py"}
    after = _digest(root)
    assert added <= set(after)
    assert {k: v for k, v in after.items() if k in before} == before
    assert _digest(REPO) == before


@pytest.mark.parametrize("reader", sorted(
    f[:-3] for f in os.listdir(os.path.join(REPO, "benchmark", "layers"))
    if f.endswith(".py")))
def test_a_reader_with_nothing_to_read_returns_nothing(bench_root, reader):
    root, spec = bench_root
    run = harness.Run(root=root, cell=spec["workloads"][0],
                      config={}, traffic={}, seed=0, seconds=1, trace=True,
                      require_platform="cpu", started_at=0.0, cache_dir="")
    assert run.module("layers", reader).read(run) is None


# -- refusing ---------------------------------------------------------------

def test_the_command_line_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for command in (["benchmark/run.py"], ["-m", "benchmark.run"]):
        got = subprocess.run(
            [sys.executable, *command, "--workload", "train_l16_1chip",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        assert got.returncode != 0
        assert got.stdout.strip() == ""
        assert "needs platform 'tpu'" in got.stderr


def test_without_the_program_there_is_no_result(bench_root):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command fails and prints no result."""
    root, _spec = bench_root
    got = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "No module named 'pytorch_distributed_mnist_tpu'" in got.stderr


def test_fewer_chips_than_the_cell_asks_for_is_refused(bench_root,
                                                        monkeypatch):
    root, spec = bench_root
    add_cell(root, spec, name="tiny_64", config=TINY_CONFIG,
             traffic={"name": "tiny_scan64", **TINY_JOB}, chips=64)
    write_spec(root, spec)
    with pytest.raises(harness.NoDevice, match="needs 64 chip"):
        run_cell(root, "tiny_64")

    def refuse(*_args, **_kwargs):
        raise harness.NoDevice("no chip")

    monkeypatch.setattr(harness, "run_cell", refuse)
    assert harness.main(["--workload", "tiny_64", "--seed", "1",
                         "--seconds", "1", "--trace", "0"]) == 3


def test_a_knob_the_runner_does_not_build_is_refused(bench_root):
    root, spec = bench_root
    add_cell(root, spec, name="tiny_flash", config=TINY_CONFIG,
             traffic={"name": "tiny_flash", **TINY_JOB,
                      "attention": "flash"}, chips=1)
    write_spec(root, spec)
    with pytest.raises(ValueError, match="attention 'flash'"):
        run_cell(root, "tiny_flash")


def test_an_unknown_cell_is_an_error(bench_root):
    root, _spec = bench_root
    with pytest.raises(SystemExit, match="no workload named 'nope'"):
        run_cell(root, "nope")


def test_process_start_precedes_the_import():
    started = harness.process_started_at()
    assert 0 <= harness._IMPORTED_AT - started < 3600


# -- BENCHMARK.json against the contract --------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    assert all(re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
               and not p.startswith("/") and ".." not in p
               for p in spec["paths"])
    assert len(spec["command"]) <= 32
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # 2 + 14 runs a cell at run_seconds + 60, 2 x 90 s a cell to compile and
    # 1200 s spare have to fit 43200 s with the full 24 cells.
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 2 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["configs"]) <= 24
    assert 1 <= len(spec["end_to_end"]) <= 16
    assert 1 <= len(spec["per_layer"]) <= 128


def test_the_command_names_only_files_of_the_benchmark(spec):
    for word in spec["command"]:
        assert 1 <= len(word) <= 200 and "\t" not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in spec["paths"])


def test_configs(spec):
    files = set()
    for cfg in spec["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(cfg["name"])
        assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
        assert any(cfg["file"].startswith(p + "/") for p in spec["paths"])
        assert cfg["file"] not in files
        files.add(cfg["file"])
        with open(os.path.join(REPO, cfg["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
        assert any(w["config"] == cfg["name"] for w in spec["workloads"])
        # Published widths, whole: arXiv:2010.11929, Table 1.
        kw, pub = body["kwargs"], body["published"]
        assert kw["embed_dim"] == pub["hidden_size"]
        assert kw["depth"] == pub["layers"]
        assert kw["num_heads"] == pub["heads"]
        assert kw["embed_dim"] * kw["mlp_ratio"] == pub["mlp_size"]
        assert kw["embed_dim"] // kw["num_heads"] == 64
        assert (28 // kw["patch_size"]) ** 2 == pub["tokens_at_224_patch_16"]
    assert len({c["name"] for c in spec["configs"]}) == len(spec["configs"])


def test_workloads(spec):
    configs = {c["name"] for c in spec["configs"]}
    seen = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        traffic = harness.load_json(os.path.join(
            REPO, "benchmark", "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "runners", f"{traffic['runner']}.py"))
    assert len({w["name"] for w in spec["workloads"]}) == len(spec["workloads"])
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(names)) == len(names)
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in end_to_end
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in end_to_end and m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layers", f"{m['name']}.py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        def reported(kind):
            return [m for m in spec[kind]
                    if cell in m.get("workloads", cells)]
        assert len(reported("end_to_end")) >= 2
        assert reported("per_layer")


def test_files_under_paths_are_named_from_a_names_characters(spec):
    for path in spec["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert re.fullmatch(r"[A-Za-z0-9_./-]+", rel), rel
