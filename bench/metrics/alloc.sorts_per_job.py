"""Sorts per simulated job of the whole-chip sweep cells: ``sort`` ops of
each executor the window ran, weighted by loop trips, times its lanes (a
batched sort sorts every lane once), over the jobs those lanes simulate
(``bench/hlo.py``)."""

from bench import hlo


def read(ctx):
    return hlo.sorts_per_job(ctx.entry.executors())
