"""Share of the device's busy time in the whole-chip sweep cell spent in
ops of the allocate layer: the top device ops that every executor the
window ran places under the ``engine.allocate`` name scope, by their
``op_name`` metadata.  A lower bound: only the trace's top ops are named
(``bench/program.py``)."""

from bench import program


def read(ctx):
    return program.scope_share(ctx, "engine.allocate")
