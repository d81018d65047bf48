"""Seconds from the process's start (``run.started_at``, the origin of
``setup_s``) to the first span a runner opens: the interpreter, the
imports, the chip's attach, the model object and the mesh (``CompileLog``'s
span ``startup``). What no change to a program moves, only a lighter
import. Layer: Entry and compile."""

from benchmark import setup_spans


def read(run):
    return setup_spans.seconds(run, "startup")
