"""Percent of the (token, choice) pairs routed in the window that landed on
an expert held here (program counter, ``routing_log``); the expectation is
held / num_experts. Layer: Step."""


def read(run):
    routing = run.counters.get("routing") or {}
    share = routing.get("local_pair_share")
    return None if share is None else 100.0 * share
