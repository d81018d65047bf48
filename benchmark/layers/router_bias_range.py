"""The range of the expert layers' selection bias (max - min over a layer's
experts after a step's update, the largest over the layers), the mean over
the window's steps: program counter, ``routing_log``
(``utils/profiling.py RoutingLog``; the step carries it out with the
routing counters). A bias that a step moves by ``gamma`` grows a range of
at most ``2 gamma`` a step; one that stays at 0 is not being moved. Layer:
Step."""


def read(run):
    routing = run.counters.get("routing") or {}
    return routing.get("bias_range")
