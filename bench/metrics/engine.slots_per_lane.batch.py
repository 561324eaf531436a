"""Per-lane width of the job state the event scan carries, in the batch
cells, read as ``engine.slots_per_lane`` is (``bench/carried.py`` on the
compiled text): Figure 4, a full-tape witness."""

from bench import carried


def read(ctx):
    return carried.slots_per_lane(ctx)
