"""Share of the traced stretch of a whole-chip sweep window in which no
operation ran on the device: 1 - (union of the chips' op intervals) /
window, averaged over the chips (``bench/devtrace.py``)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share
