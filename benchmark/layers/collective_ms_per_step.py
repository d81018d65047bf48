"""Milliseconds a step spends with a collective in flight on a chip (device
trace: union of the collective ops, an asynchronous pair counted from its
start to its done), averaged over the chips. Layer: Parallel."""


def read(run):
    c, t = run.counters, run.reduced_trace
    if t is None:
        return None
    return 1e3 * t["collective_s"] / (c["steps_per_pass"] * c["traced_passes"])
