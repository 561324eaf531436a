"""Share of the device's busy time in the Figure 4 batch cell spent in ops
under the ``engine.allocate`` name scope, read as ``alloc.device_share``
is (``bench/program.py``)."""

from bench import program


def read(ctx):
    return program.scope_share(ctx, "engine.allocate")
