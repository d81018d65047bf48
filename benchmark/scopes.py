"""Device time by the program's own scopes: the second reduction of the
``.xplane.pb`` that ``trace.py`` reduces first.

The program names its parts with ``jax.named_scope`` (flax runs every
module method under one: ``block7/attn/qkv``, ``block7/ln1``; the program
adds ``optimizer``, ``loss``, ``attn_core`` and ``mlp``), and XLA carries
the scope of an instruction, its ``op_name``, into the compiled program.
A TPU trace holds it in the stat ``tf_op`` of the event's *metadata* entry
(``jit(train_epoch)/while/body/closed_call/transpose(jvp(VisionTransformer))
/block8/attn/attn_core/reduce_sum:``), not on the event, and
``jax.profiler.ProfileData`` hands out an event's own stats only. So the
scopes are read from the file's bytes by the small wire-format reader below
(five flat messages: varints and length-delimited fields) and joined to the
events on the event's name, which is its metadata's name. Planes that are
no device's are passed over by their length.

Each op event goes to one class, by the first rule of ``CLASSES`` that its
scope matches (a collective by its instruction's name, before any scope, so
that waiting on an exchange is not charged to whoever asked for it). A
fusion has one scope, its root's: where XLA fuses across a boundary the
whole op goes to the root's class. Times are *self* times inside the
``bench:window`` span (``trace.self_times``), so the classes partition what
``trace.py`` sums as busy.

A persistent compile cache keys a program without its names (jax strips
the debug information before it hashes), so an executable that an older
checkout cached runs under that checkout's scopes. Where a trace holds
device ops and none of class ``optimizer`` or ``attn_core``, the names are
older than the source: the reduction says ``stale`` and no reader reports.
"""

from __future__ import annotations

import json
import re
import time
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import trace

SCOPE_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
TRAIN_MODULE = "jit_train_epoch"
SPAN_PREFIX = "trainer:"
ROWS = 30
UNSCOPED = "unscoped"
COLLECTIVE = "collective"
# (class, what its scope path matches); the first match wins. ``attn_proj``
# is the attention module less its core: the qkv and output projections and
# the split into heads between them. ``ends`` is what runs once a step round
# the blocks: embedding, head, loss, and what sits in the model's own scope
# (the pool, the position embedding).
CLASSES = (
    ("optimizer", re.compile(r"(^|/)optimizer(/|$)")),
    ("attn_core", re.compile(r"(^|/)attn_core(/|$)")),
    ("attn_proj", re.compile(r"/attn(/|$)")),  # qkv, proj, the head split
    ("mlp", re.compile(r"/(mlp|mlp1|mlp2)(/|$)")),
    ("norm", re.compile(r"/(ln1|ln2|ln_f)(/|$)")),
    ("ends", re.compile(r"/(embed|head)(/|$)|(^|[/(])loss[/)]"
                        r"|jvp\(\w+\)+/[^/]+$")),
)
CLASS_NAMES = (COLLECTIVE,) + tuple(c for c, _ in CLASSES) + (UNSCOPED,)
FRESH = ("optimizer", "attn_core")  # a current executable has ops of both
_BLOCK = re.compile(r"\bblock\d+\b")


# -- the file's bytes -------------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message in ``buf[lo:hi]``: a varint
    is its value, a length-delimited field the ``(lo, hi)`` of its
    payload, which is not looked into; fixed-width fields are passed over."""
    pos = lo
    while pos < hi:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, (pos, pos + size)
            pos += size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value's span of one ``map<int64, Message>`` entry."""
    for number, value in fields(buf, *span):
        if number == 2:
            return value
    return None


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Device plane name -> {event metadata name -> scope}, for the
    metadata entries that carry the stat ``SCOPE_STAT``.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (the id of a stat metadata whose name is the string)."""
    buf = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, event_meta, stat_meta = "", [], []
        for number, value in fields(buf, *plane):
            if number == 2:
                name = _text(buf, value)
            elif number == 4:
                event_meta.append(value)
            elif number == 5:
                stat_meta.append(value)
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for entry in stat_meta:
            ident, label = 0, ""
            for number, value in fields(buf, *_map_value(buf, entry)):
                if number == 1:
                    ident = value
                elif number == 2:
                    label = _text(buf, value)
            stat_names[ident] = label
        scopes = out.setdefault(name, {})
        for entry in event_meta:
            label, found = "", None
            for number, value in fields(buf, *_map_value(buf, entry)):
                if number == 2:
                    label = _text(buf, value)
                elif number == 5:
                    stat = dict(fields(buf, *value))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        found = _text(buf, stat[5])
                    elif 7 in stat:
                        found = stat_names.get(stat[7], "")
            if found:
                # "<op_name>:<op_type>", the type empty in a jax program.
                scopes[label] = found.rsplit(":", 1)[0]
    return out


# -- classes ------------------------------------------------------------------

def classify(instruction: str, scope: str) -> str:
    """The class of one op: ``instruction`` is its name (``fusion.12``),
    ``scope`` its ``op_name`` or ``""``."""
    if trace.collective_kind(instruction) is not None:
        return COLLECTIVE
    for name, pattern in CLASSES:
        if pattern.search(scope):
            return name
    return UNSCOPED


def is_backward(scope: str) -> bool:
    return "transpose(" in scope


# -- reduction ----------------------------------------------------------------

def reduce(xspace: bytes, rows: int = ROWS) -> Optional[dict]:
    """Seconds by class of scope, from a serialised XSpace; ``None`` where
    no operation ran on a device. See the module docstring."""
    from jax.profiler import ProfileData

    scopes = op_scopes(xspace)
    ops: Dict[str, List[trace.Event]] = {}
    modules: Dict[str, List[trace.Event]] = {}
    spans: List[trace.Event] = []
    window = None
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        device = bool(trace.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                event = trace.Event(ev.name, start,
                                    start + float(ev.duration_ns))
                if not device:
                    if ev.name == trace.WINDOW_SPAN:
                        window = (event.start, event.end)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append(event)
                elif line.name == trace.OPS_LINE:
                    ops.setdefault(plane.name, []).append(event)
                elif ev.name.startswith(TRAIN_MODULE):
                    modules.setdefault(plane.name, []).append(event)
    if not ops:
        return None
    if window is None:  # as trace.reduce: the extent of the device events
        window = (min(e.start for evs in ops.values() for e in evs),
                  max(e.end for evs in ops.values() for e in evs))
    lo, hi = window
    n = len(ops)
    parsed: Dict[str, Tuple[str, str, str, str]] = {}
    # Summed in nanoseconds over the chips, divided once at the end.
    by_class = {c: {"s": 0.0, "forward_s": 0.0, "backward_s": 0.0}
                for c in CLASS_NAMES}
    by_row: Dict[Tuple[str, str, str], float] = defaultdict(float)
    by_span: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = {}
    for plane_name, events in ops.items():
        names = scopes.get(plane_name, {})
        events = [trace.Event(e.name, max(e.start, lo), min(e.end, hi))
                  for e in events if min(e.end, hi) > max(e.start, lo)]
        for ev, self_ns, _leaf in trace.self_times(events):
            if ev.name not in parsed:
                instruction, group = trace.parse_hlo(ev.name)
                scope = names.get(ev.name, "")
                parsed[ev.name] = (classify(instruction, scope), scope,
                                   _BLOCK.sub("block*", scope), group)
            cls, scope, starred, group = parsed[ev.name]
            by_class[cls]["s"] += self_ns
            by_class[cls]["backward_s" if is_backward(scope)
                          else "forward_s"] += self_ns
            by_row[(cls, starred, group)] += self_ns
        covered = trace.union((e.start, e.end) for e in events)
        for gap in trace.subtract([window], covered):
            by_span[trace.attribute(gap, spans)] += gap[1] - gap[0]
        module_s[plane_name] = trace.length(trace.clip(
            [(m.start, m.end) for m in modules.get(plane_name, [])],
            lo, hi)) / 1e9

    def mean_s(ns: float) -> float:
        return ns / n / 1e9

    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "stale": not all(by_class[c]["s"] > 0 for c in FRESH),
        "busy_self_s": mean_s(sum(c["s"] for c in by_class.values())),
        "classes": {c: {k: mean_s(v) for k, v in parts.items()}
                    for c, parts in by_class.items()},
        "module_s": module_s if modules else None,
        "idle_gaps": {k: mean_s(v) for k, v in by_span.items()},
        "rows": [[*key, mean_s(ns)] for key, ns in sorted(
            by_row.items(), key=lambda kv: -kv[1])[:rows]],
    }


def _reduce_run(run) -> Optional[dict]:
    if run.reduced_trace is None:
        return None
    t0 = time.perf_counter()
    try:
        path = trace.find_xplane(run.scratch_dir("trace"))
    except FileNotFoundError:
        return None
    with open(path, "rb") as f:
        found = reduce(f.read())
    if found is None:
        return None
    found["reduce_s"] = time.perf_counter() - t0
    with open(run.out_path("scopes.json"), "w") as f:
        json.dump(found, f, indent=1)
    run.note(kind="scopes", **{k: v for k, v in found.items()
                              if k != "rows"})
    return None if found["stale"] else found


def of(run) -> Optional[dict]:
    """This run's reduction, made once and kept on ``run.counters``, written
    whole to ``<cell>.scopes.json`` and noted on an earlier line. ``None``
    where the run was not traced, the trace holds no device operation, or
    its names are stale."""
    if "scopes" not in run.counters:
        run.counters["scopes"] = _reduce_run(run)
    return run.counters["scopes"]


def _ms_per_step(run, seconds: float) -> float:
    c = run.counters
    return 1e3 * seconds / (c["steps_per_pass"] * c["traced_passes"])


def class_ms_per_step(run, name: str) -> Optional[float]:
    """Milliseconds a step spends in ops of class ``name``, mean over the
    chips: what the ``<class>_ms_per_step`` readers return."""
    found = of(run)
    if found is None:
        return None
    return _ms_per_step(run, found["classes"][name]["s"])


def module_ms_per_step(run) -> Optional[float]:
    """Milliseconds of a step inside the train epoch's module events, mean
    over the chips: what ``device_step_ms`` returns."""
    found = of(run)
    if found is None or not found["module_s"]:
        return None
    per_chip = found["module_s"].values()
    return _ms_per_step(run, sum(per_chip) / len(per_chip))
