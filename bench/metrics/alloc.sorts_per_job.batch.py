"""Sorts per simulated job of the Figure 4 batch cells, read as
``alloc.sorts_per_job`` is (``bench/hlo.py``)."""

from bench import hlo


def read(ctx):
    return hlo.sorts_per_job(ctx.entry.executors())
