"""Median milliseconds per live decision in the job-table work of
``ClusterScheduler.allocations``: the active set, the effective p and the
padded sizes (the program's ``sched.table`` spans inside the window,
``bench/program.py``)."""

import numpy as np

from bench import program


def read(ctx):
    d = program.per_request(ctx, "sched.table")
    return None if d is None else float(1e3 * np.median(d))
