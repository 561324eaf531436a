"""Share of the traced stretch of a live window in which no operation ran
on the device: 1 - (union of the chip's op intervals) / window
(``bench/devtrace.py``)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share
