"""Median milliseconds per live decision from the padded sizes to the
compiled policy's return: the sizes put on the device (``sched.put``) plus
the jitted call (``sched.call``), summed per decision (``bench/program.py``)."""

import numpy as np

from bench import program


def read(ctx):
    d = program.per_request(ctx, "sched.put", "sched.call")
    return None if d is None else float(1e3 * np.median(d))
