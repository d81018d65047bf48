"""Device time of a token model by the scopes it adds: a third reduction of
the ``.xplane.pb``, beside ``scopes.py``'s, whose class table is fixed.

The decoder (``models/decoder.py``) names ``block<i>/moe/{router, dispatch,
experts, shared, combine}`` and enters the attention core under
``attn_core/full`` or ``attn_core/window``; the flash kernels are the
Pallas calls named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``
(``ops/pallas/flash.py``). This file reads the same scopes as ``scopes.py``
(its ``op_scopes``) and the same self times inside ``bench:window``
(``trace.self_times``) and sums them by the classes below, and the kernels'
events by kernel and kind of layer, with the number of calls, so that a
roofline share can be computed from per-call costs (``flash_cost.py``).

In ``scopes.py``'s table these ops fall under ``attn_core`` (both kinds
together) and, for ``moe``, under ``unscoped``: ``scopes.CLASSES`` has no
class for them (PERF.md section 7).

A program without these scopes (the parent of the PR that adds them, a ViT
cell) gives zero seconds and zero calls everywhere; the readers then return
``None`` and the result line leaves their metrics out.
"""

from __future__ import annotations

import json
import re
import time
from collections import defaultdict
from typing import Dict, Optional

from benchmark import scopes, trace

# (class, what its scope path matches); an op may be in at most one.
CLASSES = (
    # ``)`` too: a scope entered outside a ``custom_vjp`` is printed inside
    # its wrapper, ``transpose(jvp(block1/attn/attn_core/window))/...``.
    ("moe_router", re.compile(r"/moe/router([/)]|$)")),
    ("moe_dispatch", re.compile(r"/moe/(dispatch|combine)([/)]|$)")),
    # XLA names its grouped-matmul kernel itself, ``ragged-dot-<mode>``,
    # and drops the scope its ``ragged_dot`` was called under.
    ("moe_experts", re.compile(r"/moe/(experts|shared)([/)]|$)"
                               r"|(^|/)ragged-dot")),
    ("attn_full", re.compile(r"(^|/)attn_core/full([/)]|$)")),
    ("attn_window", re.compile(r"(^|/)attn_core/window([/)]|$)")),
)
# Kernel by the Pallas call's name, found in the event's name or its scope.
KERNELS = (
    ("flash_fwd", re.compile(r"flash_fwd")),
    ("flash_bwd_dq", re.compile(r"flash_bwd_dq")),
    ("flash_bwd_dkv", re.compile(r"flash_bwd_dkv")),
)
KINDS = ("full", "window")
ROWS = 40


def classify(scope: str) -> Optional[str]:
    for name, pattern in CLASSES:
        if pattern.search(scope):
            return name
    return None


def kernel_of(event_name: str, scope: str) -> Optional[str]:
    for name, pattern in KERNELS:
        if pattern.search(event_name) or pattern.search(scope):
            return name
    return None


def kind_of(scope: str) -> Optional[str]:
    for kind in KINDS:
        if f"attn_core/{kind}" in scope:
            return kind
    return None


def reduce(xspace: bytes, rows: int = ROWS) -> Optional[dict]:
    """Seconds by class and by kernel, mean over the chips, from a
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
                if device:
                    start = float(ev.start_ns)
                    ops.setdefault(plane.name, []).append(trace.Event(
                        ev.name, start, start + float(ev.duration_ns)))
                elif ev.name == trace.WINDOW_SPAN:
                    start = float(ev.start_ns)
                    window = (start, start + float(ev.duration_ns))
    if not ops:
        return None
    if window is None:
        window = (min(e.start for evs in ops.values() for e in evs),
                  max(e.end for evs in ops.values() for e in evs))
    lo, hi = window
    by_class = {name: 0.0 for name, _ in CLASSES}
    kernels = {name: {kind: {"s": 0.0, "calls": 0} for kind in KINDS}
               for name, _ in KERNELS}
    by_row = defaultdict(float)
    for plane_name, events in ops.items():
        scope_of = names.get(plane_name, {})
        events = [trace.Event(e.name, max(e.start, lo), min(e.end, hi))
                  for e in events if min(e.end, hi) > max(e.start, lo)]
        for ev, self_ns, _leaf in trace.self_times(events):
            scope = scope_of.get(ev.name, "")
            cls = classify(scope)
            if cls is not None:
                by_class[cls] += self_ns
                by_row[(cls, scopes._BLOCK.sub("block*", scope),
                        trace.parse_hlo(ev.name)[1])] += self_ns
            kernel, kind = kernel_of(ev.name, scope), kind_of(scope)
            if kernel is not None and kind is not None:
                kernels[kernel][kind]["s"] += self_ns
                kernels[kernel][kind]["calls"] += 1
    n = len(ops)
    for per_kind in kernels.values():
        for cell in per_kind.values():
            cell["s"] = cell["s"] / n / 1e9
            cell["calls"] = cell["calls"] / n
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "classes": {k: v / n / 1e9 for k, v in by_class.items()},
        "kernels": kernels,
        "rows": [[*key, ns / n / 1e9] for key, ns in sorted(
            by_row.items(), key=lambda kv: -kv[1])[:rows]],
    }


def of(run) -> Optional[dict]:
    """This run's reduction, made once and kept on ``run.counters``, written
    whole to ``<cell>.scopes_lm.json``. ``None`` where the run was not
    traced or its names are stale (``scopes.of``)."""
    if "scopes_lm" not in run.counters:
        found = None
        if scopes.of(run) is not None:
            t0 = time.perf_counter()
            with open(trace.find_xplane(run.scratch_dir("trace")),
                      "rb") as f:
                found = reduce(f.read())
            if found is not None:
                found["reduce_s"] = time.perf_counter() - t0
                with open(run.out_path("scopes_lm.json"), "w") as f:
                    json.dump(found, f, indent=1)
                run.note(kind="scopes_lm", **{
                    k: v for k, v in found.items() if k != "rows"})
        run.counters["scopes_lm"] = found
    return run.counters["scopes_lm"]


def _steps(run) -> int:
    return run.counters["steps_per_pass"] * run.counters["traced_passes"]


def class_ms_per_step(run, *names: str) -> Optional[float]:
    """Milliseconds a step spends in ops of the classes ``names``; ``None``
    where the program has no op of any of them."""
    found = of(run)
    if found is None:
        return None
    seconds = sum(found["classes"][name] for name in names)
    return 1e3 * seconds / _steps(run) if seconds > 0 else None
