"""Milliseconds a training step takes: the median pass's host-clock wall
(a pass ends in a host read) over its steps. Layer: Step."""


def read(run):
    c = run.counters
    if "median_pass_s" not in c:
        return None
    return 1e3 * c["median_pass_s"] / c["steps_per_pass"]
