"""Event-scan trips per simulated job of the whole-chip sweep cells: the
event scan's trip count of each executor the window ran, times its lanes,
over the jobs those lanes simulate (``bench/hlo.py`` on the compiled text)."""

from bench import hlo


def read(ctx):
    return hlo.trips_per_job(ctx.entry.executors())
