"""Host milliseconds per executor call of the Figure 4 batch cell: the
program's ``sweep.prepare``, ``sweep.executor``, ``sweep.dispatch`` and
``sweep.fetch`` spans inside the window, over the number of
``sweep.dispatch`` spans (``bench/program.py``).  The wait for the device
(``sweep.wait``) is left out."""

from bench import program


def read(ctx):
    got = program.spans(ctx, ("sweep.prepare", "sweep.executor", "sweep.dispatch",
                              "sweep.fetch"))
    if got is None:
        return None
    calls = sum(s.name == "sweep.dispatch" for s in got)
    if not calls:
        return None
    return 1e3 * sum(s.end - s.start for s in got) / calls
