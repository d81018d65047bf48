"""Set-up as ``CompileLog`` keeps it (``utils/profiling.py``, since PR 35):
the spans that every runner's ``compile_log.measure(...)`` opens, with
their starts and ends, and ``startup``, from the process's start to the
first of them. The readers ``layers/startup_s.py``, ``state_init_s.py``,
``reference_check_s.py`` and ``setup_unaccounted_s.py`` share this.

Set-up ends with the warming pass: the top-level spans (those opened inside
no other on their thread) up to the end of ``train_pass`` are

    startup | init | reference_check | init_state | train_pass

in whatever order and number the runner opens them, and what lies between
them is ``setup_unaccounted_s``. The log is read when the readers run,
after the window, which opens no span. Against a program whose
``CompileLog`` keeps no spans every function here returns ``None``.
"""

NAMED = ("startup", "init", "init_state", "reference_check", "train_pass")


def top_level(run):
    """The finished top-level spans of set-up, in order, or ``None`` where
    the run kept nothing or the program keeps no spans."""
    if run.counters.get("compile") is None:
        return None
    from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

    spans = [s for s in compile_log.stats().get("spans", ())
             if s["parent"] is None and s["end_unix"] is not None]
    ends = [s["end_unix"] for s in spans if s["name"] == "train_pass"]
    if not ends or spans[0]["name"] != "startup":
        return None
    return [s for s in spans if s["end_unix"] <= ends[0]]


def _named_seconds(run, spans, names):
    """``startup`` counts from ``run.started_at``, the origin ``setup_s``
    is counted from (the span's own start is the process's, which a
    test's origin is not), so that the parts add up to ``setup_s``."""
    return sum(
        s["end_unix"] - (run.started_at if s["name"] == "startup"
                         else s["start_unix"])
        for s in spans if s["name"] in names)


def seconds(run, *names):
    """Seconds that set-up's top-level spans called one of ``names`` took
    together."""
    spans = top_level(run)
    if spans is None:
        return None
    return _named_seconds(run, spans, names)


def unaccounted(run):
    """Seconds from ``run.started_at`` to the end of the warming pass that
    lie inside none of the :data:`NAMED` spans."""
    spans = top_level(run)
    if spans is None:
        return None
    whole = max(s["end_unix"] for s in spans) - run.started_at
    return whole - _named_seconds(run, spans, NAMED)
