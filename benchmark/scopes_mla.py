"""Device time of a latent-attention decoder with a multi-token-prediction
module by the scopes it adds: a reduction of the ``.xplane.pb`` beside
``scopes.py``'s, whose class table is fixed, ``scopes_lm.py``'s (the expert
layers, the attention cores, the flash kernels) and ``scopes_ssm.py``'s.

``models/decoder.py LatentAttention`` names ``attn/mla/{q, kv_a, kv_b,
rope, gate, proj}`` round what is not the attention core (which stays under
``attn_core/full``); ``models/instella.py`` runs its module under
``mtp/{merge, mtp_block, head}`` and ``train/steps.py`` its loss under
``mtp/loss``. This file reads the same scopes as ``scopes.py`` (its
``op_scopes``) and the same self times inside ``bench:window``
(``trace.self_times``) and sums them by the classes below. The classes
are not exclusive: the module's own latent attention
(``mtp/mtp_block/attn/mla``) counts under ``mla_proj`` and under ``mtp``,
since each metric says "every op under" its scope. Forward, recomputed
forward and backward are all counted, and split by ``transpose(`` in the
scope as ``scopes.py`` splits every class.

In ``scopes.py``'s table the ``attn/mla`` ops fall under ``attn_proj``, the
module's under whatever their inner scope names (``attn_core``,
``attn_proj``, ``norm``, ``ends``) or ``unscoped``, as is the update of the
selection bias (``train/steps.py``, scope ``moe/bias``: a few microseconds
a step, which no metric reads; PERF.md section 3).

A program without these scopes (the parent of the PR that adds them, any
other cell) gives zero seconds everywhere; the readers then return ``None``
and the result line leaves their metrics out.
"""

from __future__ import annotations

import json
import re
import time
from collections import defaultdict
from typing import Dict, Optional

from benchmark import scopes, trace

# (class, what its scope path matches). ``)`` too: a scope entered outside a
# ``custom_vjp`` is printed inside its wrapper.
CLASSES = (
    ("mla_proj", re.compile(r"/attn/mla([/)]|$)")),
    ("mtp", re.compile(r"(^|[/(])mtp([/)]|$)")),
)
ROWS = 40


def classify(scope: str) -> list:
    """The classes ``scope`` belongs to (none, one or more)."""
    return [name for name, pattern in CLASSES if pattern.search(scope)]


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
    by_class = {name: {"s": 0.0, "forward_s": 0.0, "backward_s": 0.0}
                for name, _ in CLASSES}
    by_row = defaultdict(float)
    for plane_name, events in ops.items():
        scope_of = names.get(plane_name, {})
        events = [trace.Event(e.name, max(e.start, lo), min(e.end, hi))
                  for e in events if min(e.end, hi) > max(e.start, lo)]
        for ev, self_ns, _leaf in trace.self_times(events):
            scope = scope_of.get(ev.name, "")
            for cls in classify(scope):
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
    whole to ``<cell>.scopes_mla.json``. ``None`` where the run was not
    traced or its names are stale (``scopes.of``)."""
    if "scopes_mla" not in run.counters:
        found = None
        if scopes.of(run) is not None:
            t0 = time.perf_counter()
            with open(trace.find_xplane(run.scratch_dir("trace")),
                      "rb") as f:
                found = reduce(f.read())
            if found is not None:
                found["reduce_s"] = time.perf_counter() - t0
                with open(run.out_path("scopes_mla.json"), "w") as f:
                    json.dump(found, f, indent=1)
                run.note(kind="scopes_mla", **{
                    k: v for k, v in found.items() if k != "rows"})
        run.counters["scopes_mla"] = found
    return run.counters["scopes_mla"]


def class_ms_per_step(run, name: str) -> Optional[float]:
    """Milliseconds a step spends in ops of class ``name``; ``None`` where
    the program has no op of it."""
    found = of(run)
    if found is None:
        return None
    seconds = found["classes"][name]["s"]
    steps = run.counters["steps_per_pass"] * run.counters["traced_passes"]
    return 1e3 * seconds / steps if seconds > 0 else None
