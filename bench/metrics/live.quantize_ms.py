"""Median milliseconds per decision in the rounding of the policy's shares
to whole chips on the host (``live.quantize`` spans of the traced window)."""

import numpy as np


def read(ctx):
    d = ctx.spans.durations("live.quantize")
    return float(1e3 * np.median(d)) if d else None
