"""Bytes of state a selective scan keeps between the passes, a traced scan
(program counter, ``utils/profiling.py ScanLog``): the states at its
chunks' starts, all it holds of the ``(T, C, N)`` states (5.4 GB a sequence
at T = 16,384, C = 5,120, N = 16 in float32). Layer: Step."""


def read(run):
    scan = run.counters.get("scan") or {}
    return scan.get("state_bytes_kept_per_site")
