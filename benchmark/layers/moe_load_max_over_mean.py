"""Tokens of the fullest held expert over the mean of the held experts, a
layer and step, averaged over the window's layers and steps (program
counter: ``MetricState.routing`` read with the pass's metrics into
``utils.profiling.routing_log``). Layer: Step."""


def read(run):
    routing = run.counters.get("routing") or {}
    return routing.get("load_max_over_mean")
