"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that builds the cell's system from the seed, warms every
shape up (set-up), measures for ``--seconds`` and prints, as the last line
of its standard output, one JSON object with exactly the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``. Anything else worth reading goes on earlier lines (JSON,
one object a line) or into ``chiprun_out/benchmark/``.

The command line has no switch for the platform: without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result. Tests call :func:`run_cell` with the requirement passed in.

The harness is driven by data. ``BENCHMARK.json`` names the cell's
configuration file and traffic mix; ``traffic/<mix>.json`` names its
runner, ``runners/<runner>.py``; each per-layer metric is read by
``layers/<metric>.py``. All are found by name, below ``root``.
"""

from __future__ import annotations

import os
import sys
import time

_IMPORTED_AT = time.time()
_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH_DIR)
# Run as a script, python puts benchmark/ itself first on the path, where
# trace.py would hide the standard library's module of that name.
if sys.path and os.path.abspath(sys.path[0] or ".") == _BENCH_DIR:
    del sys.path[0]
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

BENCH_DIRNAME = os.path.basename(_BENCH_DIR)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class NoDevice(RuntimeError):
    """The machine does not hold what the cell asks for: no result."""


def process_started_at() -> float:
    """Unix time at which this process started, from ``/proc`` (its start
    and the machine's uptime are both counted from boot, to 10 ms), so that
    ``setup_s`` includes the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 24 * 3600:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED_AT


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """A module from a file found by name (runner, layer reader, hook)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{name.replace('/', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}; "
                     f"it has {[e['name'] for e in entries]}")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    """What a runner is handed: the cell, its files, the device requirement
    and somewhere to leave what is too long for a line."""

    def __init__(self, *, root, cell, config, traffic, seed, seconds,
                 trace, require_platform, started_at, cache_dir):
        self.root = root
        self.bench_dir = os.path.join(root, BENCH_DIRNAME)
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.chips = cell["chips"]
        self.require_platform = require_platform
        self.started_at = started_at
        self.cache_dir = cache_dir
        # Kept by the run, read by the layer readers.
        self.counters: Dict[str, Any] = {}
        self.reduced_trace: Optional[dict] = None
        self._modules: Dict[str, Any] = {}

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py`` of this checkout's benchmark, loaded once."""
        key = f"{kind}/{name}"
        if key not in self._modules:
            self._modules[key] = load_module(
                os.path.join(self.bench_dir, f"{key}.py"), key)
        return self._modules[key]

    def scratch_dir(self, *parts: str) -> str:
        """A fixed directory inside the checkout for what a run writes and
        nobody keeps (the raw trace)."""
        path = os.path.join(self.root, ".bench_tmp", self.cell["name"],
                            *parts)
        os.makedirs(path, exist_ok=True)
        return path

    def out_path(self, name: str) -> str:
        """A file under ``chiprun_out/benchmark/``, which the chip tool
        copies back."""
        path = os.path.join(self.root, "chiprun_out", "benchmark")
        os.makedirs(path, exist_ok=True)
        return os.path.join(path, f"{self.cell['name']}.{name}")

    def note(self, **fields) -> None:
        """An earlier line of standard output: one JSON object."""
        print(json.dumps({"cell": self.cell["name"], **fields},
                         default=float), flush=True)

    def devices(self):
        """The chips the cell runs on; raises :class:`NoDevice` where jax
        finds another platform or fewer of them."""
        import jax

        devices = jax.devices()
        platform = devices[0].platform
        if platform != self.require_platform:
            raise NoDevice(
                f"cell {self.cell['name']!r} needs platform "
                f"{self.require_platform!r}; jax found {platform!r}")
        if len(devices) < self.chips:
            raise NoDevice(
                f"cell {self.cell['name']!r} needs {self.chips} chip(s); "
                f"jax found {len(devices)}")
        return devices[:self.chips]


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip. The TPU runtime counts what
    arrays hold (``peak_bytes_in_use``) apart from the scratch memory it
    reserves for running programs (``peak_bytes_reserved``: a program's
    temporaries, to the byte of ``memory_analysis().temp_size_in_bytes``);
    while a pass runs the chip holds both, so the peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def device_report(devices, trace: Optional[dict]) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": memory_peak_bytes(devices)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = _ROOT, require_platform: str = "tpu",
             started_at: Optional[float] = None,
             cache_dir: Optional[str] = None) -> dict:
    """Run one cell and return the final line's object.

    ``require_platform`` is what jax has to find (the command line always
    asks for ``tpu``; the CPU tests of the harness pass ``cpu``).
    ``cache_dir`` is where the persistent compile cache goes: by default
    ``<root>/.xla_cache``, unless ``JAX_COMPILATION_CACHE_DIR`` names
    another; ``""`` turns it off.
    """
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = _named(spec["workloads"], workload, "workload")
    config_entry = _named(spec["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(os.path.join(
        root, BENCH_DIRNAME, "traffic", f"{cell['traffic']}.json"))
    run = Run(root=root, cell=cell, config=config,
              traffic=traffic, seed=seed, seconds=seconds, trace=trace,
              require_platform=require_platform,
              started_at=started_at or process_started_at(),
              cache_dir=os.path.join(root, ".xla_cache")
              if cache_dir is None else cache_dir)
    result = run.module("runners", traffic["runner"]).run(run)
    device = device_report(result["devices"], run.reduced_trace)
    run.counters["memory_peak_bytes"] = device["memory_peak_bytes"]

    end_to_end = result["end_to_end"]
    if trace:
        metrics = {}
        for metric in spec["per_layer"]:
            if not _in_cell(metric, workload) \
                    or metric["moves"] not in end_to_end:
                continue
            value = run.module("layers", metric["name"]).read(run)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
    else:
        metrics = {
            m["name"]: {"value": float(end_to_end[m["name"]]),
                        "unit": m["unit"]}
            for m in spec["end_to_end"]
            if _in_cell(m, workload) and m["name"] in end_to_end}
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if run.reduced_trace is not None:
        line["breakdown"] = {
            "device_ops": run.reduced_trace["device_ops"],
            "idle_gaps": run.reduced_trace["idle_gaps"]}
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoDevice as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
