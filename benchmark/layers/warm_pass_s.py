"""Wall seconds of the first, warming pass: trace, lower, compile or cache
load, and one execution of the pass's program. Layer: Entry and compile."""


def read(run):
    return run.counters.get("warm_pass_s")
