"""Seconds of set-up that jax spent tracing Python into jaxprs
(``CompileLog`` totals at the window's start: the union of
jax.monitoring's trace intervals, which nest, never their sum). What
unrolled depth and every added kernel body or loop cost before a backend
sees them. Layer: Entry and compile."""


def read(run):
    compile_stats = run.counters.get("compile")
    if compile_stats is None or "trace_ms" not in compile_stats:
        return None
    return compile_stats["trace_ms"] / 1e3
