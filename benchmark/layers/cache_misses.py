"""Programs that were really compiled during set-up: persistent-cache
misses counted by ``CompileLog``. 0 in every run after a checkout's first.
Layer: Entry and compile."""


def read(run):
    compile_stats = run.counters.get("compile")
    if compile_stats is None:
        return None
    return compile_stats["cache_misses"]
