"""Share of the device's busy time in the shared-training-pod cell spent
in ops under the ``engine.cap`` name scope: admission, capped water-fill
and rounding within the per-job width limits.  A lower bound, as every
scope share is (``bench/program.py``); ``None`` for a program without
the scope."""

from bench import program


def read(ctx):
    return program.scope_share(ctx, "engine.cap")
