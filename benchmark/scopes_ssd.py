"""Device time of a Mamba-2 hybrid by the scopes it adds: a reduction of
the ``.xplane.pb`` beside ``scopes_ssm.py``'s, which reads the selective
scan's scopes (``ssm``), and like it beside ``scopes.py``'s, whose class
table is fixed.

``models/granite.py`` names ``block<i>/ssd/{in_proj, conv, dt, scan, norm,
out_proj}``, the kernels under ``ssd/scan`` by their own names (``ssd_fwd``,
``ssd_bwd``). This file reads the same scopes as ``scopes.py`` (its
``op_scopes``) and the same self times inside ``bench:window``
(``trace.self_times``) and sums them by the two classes below, first match
first, so that every op under an ``ssd`` scope is in exactly one of
``ssd_scan`` and ``ssd_proj``. The split into forward and backward is
``scopes.py``'s (``transpose(`` in the scope): a recomputed forward counts
as backward. What it can import of ``scopes_ssm.py`` it does
(``traced_steps``); the reduction itself is written a fifth time because
each of the four there is closes over its own table (PERF.md section 7).

In ``scopes.py``'s table the ``ssd`` ops fall under ``unscoped``, as
``ssm`` does; the attention layer's and the MLPs' under the classes they
always had.

A program without these scopes (the parent of the PR that adds them, any
other cell) gives zero seconds everywhere; the readers then return
``None`` and the result line leaves their metrics out.
"""

from __future__ import annotations

import json
import re
import time
from collections import defaultdict
from typing import Dict, Optional

from benchmark import scopes, trace
from benchmark.scopes_ssm import traced_steps

# (class, what its scope path matches); the first match wins. ``)`` too: a
# scope entered outside a ``custom_vjp`` is printed inside its wrapper.
CLASSES = (
    ("ssd_scan", re.compile(r"/ssd/scan([/)]|$)")),
    ("ssd_proj", re.compile(r"/ssd([/)]|$)")),
)
ROWS = 40

__all__ = ["CLASSES", "classify", "reduce", "of", "traced_steps",
           "class_ms_per_step"]


def classify(scope: str) -> Optional[str]:
    for name, pattern in CLASSES:
        if pattern.search(scope):
            return name
    return None


def reduce(xspace: bytes, rows: int = ROWS) -> Optional[dict]:
    """Seconds by class, forward and backward, mean over the chips, from a
    serialised XSpace; ``None`` where no operation ran on a device."""
    from jax.profiler import ProfileData

    names = scopes.op_scopes(xspace)
    ops: Dict[str, list] = {}
    window = None
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        device = bool(trace.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                if device:
                    ops.setdefault(plane.name, []).append(trace.Event(
                        ev.name, start, start + float(ev.duration_ns)))
                elif ev.name == trace.WINDOW_SPAN:
                    window = (start, start + float(ev.duration_ns))
    if not ops:
        return None
    if window is None:
        window = (min(e.start for evs in ops.values() for e in evs),
                  max(e.end for evs in ops.values() for e in evs))
    lo, hi = window
    by_class = {name: {"s": 0.0, "forward_s": 0.0, "backward_s": 0.0}
                for name, _ in CLASSES}
    by_row = defaultdict(float)
    for plane_name, events in ops.items():
        scope_of = names.get(plane_name, {})
        events = [trace.Event(e.name, max(e.start, lo), min(e.end, hi))
                  for e in events if min(e.end, hi) > max(e.start, lo)]
        for ev, self_ns, _leaf in trace.self_times(events):
            scope = scope_of.get(ev.name, "")
            cls = classify(scope)
            if cls is None:
                continue
            by_class[cls]["s"] += self_ns
            by_class[cls]["backward_s" if scopes.is_backward(scope)
                          else "forward_s"] += self_ns
            by_row[(cls, scopes._BLOCK.sub("block*", scope),
                    trace.parse_hlo(ev.name)[1])] += self_ns
    n = len(ops)
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "classes": {c: {k: v / n / 1e9 for k, v in parts.items()}
                    for c, parts in by_class.items()},
        "rows": [[*key, ns / n / 1e9] for key, ns in sorted(
            by_row.items(), key=lambda kv: -kv[1])[:rows]],
    }


def of(run) -> Optional[dict]:
    """This run's reduction, made once and kept on ``run.counters``, written
    whole to ``<cell>.scopes_ssd.json``. ``None`` where the run was not
    traced or its names are stale (``scopes.of``)."""
    if "scopes_ssd" not in run.counters:
        found = None
        if scopes.of(run) is not None:
            t0 = time.perf_counter()
            with open(trace.find_xplane(run.scratch_dir("trace")),
                      "rb") as f:
                found = reduce(f.read())
            if found is not None:
                found["reduce_s"] = time.perf_counter() - t0
                with open(run.out_path("scopes_ssd.json"), "w") as f:
                    json.dump(found, f, indent=1)
                run.note(kind="scopes_ssd", **{
                    k: v for k, v in found.items() if k != "rows"})
        run.counters["scopes_ssd"] = found
    return run.counters["scopes_ssd"]


def class_ms_per_step(run, name: str) -> Optional[float]:
    """Milliseconds a step spends in ops of class ``name``; ``None`` where
    the program has no op of it."""
    found = of(run)
    if found is None:
        return None
    seconds = found["classes"][name]["s"]
    return 1e3 * seconds / traced_steps(run) if seconds > 0 else None
