"""Bytes of state a chunked state-space scan keeps between the passes, a
traced scan (program counter, ``utils/profiling.py ScanLog``, the
``chunked_`` keys): the float32 states at its chunks' starts, all it holds
of the per-position states (17 GB a sequence at T = 8,192 and 64 heads of
64 x 128). ``None`` where the program counts no such scan. Layer: Step."""


def read(run):
    scan = run.counters.get("scan") or {}
    return scan.get("chunked_state_bytes_kept_per_site")
