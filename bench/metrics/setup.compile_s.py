"""Seconds of set-up in which JAX traced, lowered or compiled (or loaded
from the persistent cache) a program: the union of the program's compile
events (``repro.tracing.compiles()``) that ended before the window
(``bench/program.py``)."""

from bench import program


def read(ctx):
    return program.compile_seconds_before_window(ctx)
