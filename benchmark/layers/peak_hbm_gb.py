"""Peak device memory on the fullest chip, set-up included: what arrays
held plus the scratch the runtime reserved for running programs
(``run.py memory_peak_bytes``, from ``memory_stats()``). Layer: Device."""


def read(run):
    peak = run.counters.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
