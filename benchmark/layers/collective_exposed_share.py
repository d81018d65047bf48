"""Share of the traced window in which a collective was in flight and no
other op ran on that chip (device trace), averaged over the chips: the
communication the schedule failed to hide. Layer: Parallel."""


def read(run):
    t = run.reduced_trace
    if t is None:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
