"""Seconds of set-up that jax spent lowering jaxprs to MLIR modules
(``CompileLog`` totals at the window's start: the union of
jax.monitoring's lowering intervals). Layer: Entry and compile."""


def read(run):
    compile_stats = run.counters.get("compile")
    if compile_stats is None or "lower_ms" not in compile_stats:
        return None
    return compile_stats["lower_ms"] / 1e3
