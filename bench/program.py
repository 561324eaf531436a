"""What the program records about itself, read for the per-layer metrics.

- its spans (``repro.tracing.records()``) that lie inside the benchmark's
  own ``bench.window`` span;
- its compile events (``repro.tracing.compiles()``) that ended before the
  window;
- the name scopes (``jax.named_scope``) of its compiled ops, from the
  ``op_name`` metadata of the executors' compiled text.

These are read only from a run whose profiler captured the device
(``ctx.trace``), as the device shares are; a run without one reports none
of them.  Where the program records no such thing (it has no
``repro.tracing``, or no op carries the scope), a reader gets ``None`` and
reports nothing: it never raises for it.
"""

from __future__ import annotations

import re

from bench import hlo
from bench.devtrace import WINDOW_SPAN

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _tracing():
    try:
        from repro import tracing
    except ImportError:  # a program from before its own spans
        return None
    return tracing


def window(ctx) -> tuple[float, float] | None:
    """Start and end of the ``bench.window`` span, on ``time.perf_counter``."""
    return next(((a, b) for n, a, b in ctx.spans.records if n == WINDOW_SPAN), None)


def spans(ctx, names) -> list | None:
    """The program's spans called one of ``names`` inside the window."""
    tracing = _tracing()
    w = window(ctx)
    if ctx.trace is None or tracing is None or w is None:
        return None
    got = [s for s in tracing.records()
           if s.name in names and s.start >= w[0] and s.end <= w[1]]
    return got or None


def per_request(ctx, *names) -> list[float] | None:
    """Seconds in spans called one of ``names``, summed per request id."""
    got = spans(ctx, names)
    if got is None:
        return None
    by: dict = {}
    for s in got:
        by[s.request] = by.get(s.request, 0.0) + (s.end - s.start)
    return list(by.values())


def compile_seconds_before_window(ctx) -> float | None:
    """Seconds in which tracing, lowering or compiling ran before the
    window: the union of the events' intervals, since a jit traced inside
    another one's tracing reports a span within the outer span."""
    tracing = _tracing()
    w = window(ctx)
    if ctx.trace is None or tracing is None or w is None:
        return None
    ivs = sorted((c.end - c.seconds, c.end) for c in tracing.compiles() if c.end <= w[0])
    if not ivs:
        return None
    total, (lo, hi) = 0.0, ivs[0]
    for a, b in ivs[1:]:
        if a > hi:
            total, lo, hi = total + hi - lo, a, b
        else:
            hi = max(hi, b)
    return total + hi - lo


def _scopes(text: str) -> dict[str, set[str]]:
    """``{op name: op_name metadata}`` of every op of a compiled text: an
    op's own, or where it has none (a fusion, on the TPU), every one found
    in the computations it calls."""
    comps = hlo.parse_computations(text)
    memo: dict[str, set[str]] = {}

    def inside(comp_name: str, depth: int = 0) -> set[str]:
        if comp_name in memo or depth > 8 or comp_name not in comps:
            return memo.get(comp_name, set())
        found: set[str] = set()
        for op in comps[comp_name].ops:
            found |= of(op, depth + 1)
        memo[comp_name] = found
        return found

    def of(op, depth: int = 0) -> set[str]:
        own = set(_OP_NAME.findall(op.rest))
        if own:
            return own
        out: set[str] = set()
        for kind, name in hlo._called(op.rest):
            if kind in ("calls", "branch"):
                out |= inside(name, depth)
        return out

    return {op.name: of(op) for comp in comps.values() for op in comp.ops}


def scope_share(ctx, scope: str) -> float | None:
    """Share of the device's busy time spent in the top device ops that
    every executor holding an op of that name places under ``scope`` (all
    of its ``op_name`` metadata inside the scope): a lower bound, since
    only the trace's top ops are named.  ``None`` without a device trace,
    or where no op of any executor carries the scope."""
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    # A scope reads "a/engine.allocate/b", or "vmap(engine.allocate)/b"
    # where a transform opened it.
    under = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
    tables = [_scopes(text) for text, _, _ in ctx.entry.executors()]
    if not any(under.search(n) for t in tables for names in t.values() for n in names):
        return None
    seconds = 0.0
    for label, s in ctx.trace.device_ops:
        name = label.split(":", 1)[0]
        held = [t[name] for t in tables if name in t]
        if held and all(names and all(under.search(n) for n in names) for names in held):
            seconds += s
    return seconds / ctx.trace.busy_s
