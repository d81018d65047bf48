"""Share of the window the trainer's consumer thread was blocked waiting
for staged input (``StagingLog.record_wait``, reset at the window's start).
Layer: Input."""


def read(run):
    staging = run.counters.get("staging")
    if staging is None:
        return None
    return 100.0 * staging["consumer_wait_ms"] / 1e3 / run.counters["window_s"]
