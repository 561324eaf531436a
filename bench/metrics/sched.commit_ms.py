"""Median milliseconds per live decision in writing the chips to the job
table and the event log (the program's ``sched.commit`` spans,
``bench/program.py``)."""

import numpy as np

from bench import program


def read(ctx):
    d = program.per_request(ctx, "sched.commit")
    return None if d is None else float(1e3 * np.median(d))
