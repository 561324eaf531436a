"""Event-scan trips per simulated job of the Figure 4 batch cells, read as
``engine.trips_per_job`` is (``bench/hlo.py`` on the compiled text)."""

from bench import hlo


def read(ctx):
    return hlo.trips_per_job(ctx.entry.executors())
