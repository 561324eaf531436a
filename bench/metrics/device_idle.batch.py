"""Share of the traced stretch of a Figure 4 batch window in which no
operation ran on the device, read as ``device_idle.sweep`` is
(``bench/devtrace.py``)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share
