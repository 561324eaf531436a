"""Median milliseconds per decision in the compiled policy call, through
its result on the host (``live.policy`` spans of the traced window)."""

import numpy as np


def read(ctx):
    d = ctx.spans.durations("live.policy")
    return float(1e3 * np.median(d)) if d else None
