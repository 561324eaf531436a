"""Per-lane width of the job state the event scan carries, in the
whole-chip sweep cells: the widest trailing dimension of what the scan of
each executor the window ran updates on every trip (``bench/carried.py``
on the compiled text): the tape's jobs on a full tape, the slots of a
pool."""

from bench import carried


def read(ctx):
    return carried.slots_per_lane(ctx)
