"""Seconds XLA's backend spent compiling, or loading executables from the
persistent cache, during set-up (``CompileLog`` totals: jax.monitoring's
backend-compile durations). Layer: Entry and compile."""


def read(run):
    compile_stats = run.counters.get("compile")
    if compile_stats is None:
        return None
    return compile_stats["backend_compile_ms"] / 1e3
