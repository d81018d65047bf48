"""Seconds of the benchmark's own check against the plain reference inside
``setup_s`` (span ``reference_check``): what ``setup_s`` would lose if its
end were moved before the check. Layer: Entry and compile."""

from benchmark import setup_spans


def read(run):
    return setup_spans.seconds(run, "reference_check")
