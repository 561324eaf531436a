"""Share of the device's busy time in the shared-training-pod cell spent
in ops under the ``engine.snap`` name scope: the slice snap's upgrade
loop, which under ``vmap`` runs as many rounds as the worst lane.  A lower
bound, as every scope share is (``bench/program.py``); ``None`` for a
program without the scope."""

from bench import program


def read(ctx):
    return program.scope_share(ctx, "engine.snap")
