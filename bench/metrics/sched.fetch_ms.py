"""Median milliseconds per live decision in waiting for the policy's
shares and copying them to the host (the program's ``sched.fetch`` spans,
``bench/program.py``)."""

import numpy as np

from bench import program


def read(ctx):
    d = program.per_request(ctx, "sched.fetch")
    return None if d is None else float(1e3 * np.median(d))
