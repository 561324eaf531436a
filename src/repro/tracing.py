"""The program's own spans and compile counter.

``span(name)`` marks one step of a layer's work::

    with tracing.span("sched.decision", request=n):
        with tracing.span("sched.table"):
            ...

A span is recorded only while a profiler session is active
(``jax.profiler.trace``, ``jax.profiler.start_trace``, ``benchmarks/run.py
--profile-dir``): then it keeps its name, start and end on
``time.perf_counter``, the span open around it (``parent``) and a request
id, which it takes from its parent when not given, and it also opens a
``jax.profiler.TraceAnnotation`` of its name, so the profiler's trace shows
it on the device's clock.  With no session, ``span`` returns one shared
no-op context.  Spans are kept in a bounded buffer: ``records()`` reads it,
``clear()`` empties it.

The compile counter is always on: a ``jax.monitoring`` listener keeps
every tracing, lowering and backend-compile event (seconds, the function's
name, end on ``time.perf_counter``).  They fire only when something
compiles; on jax 0.9 a persistent-cache hit fires them too, with the
backend step then the cache read.  ``compiles()`` reads them.  Beside it,
``count(name)`` bumps a named counter, also always on, which ``counts()``
reads: ``engine.pool_overflows`` counts the sweep calls a slot pool could
not hold, played again on the full tape (``core/sweeps.py``).

Spans placed in the program (each span's metric is named in PERF.md):

- ``sched/cluster.py`` ``ClusterScheduler.allocations``: ``sched.decision``
  around ``sched.table``, ``sched.put``, ``sched.call``, ``sched.fetch``,
  ``sched.quantize``, ``sched.commit``;
- ``core/sweeps.py`` ``run_sweep``: ``sweep.prepare``, then per policy
  ``sweep.executor``, ``sweep.dispatch``, ``sweep.wait``, ``sweep.fetch``.

The event scans carry ``jax.named_scope`` names instead (``engine.sample``,
``engine.allocate``, ``engine.advance``, ``engine.reduce``): metadata of
the compiled program's ops, which a device trace's ops can be matched by.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax
from jax._src import monitoring as _monitoring

MAX_RECORDS = 1 << 18  # per buffer; the oldest records go first

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


class Span(NamedTuple):
    id: int  # in the order spans opened
    name: str
    start: float  # time.perf_counter seconds
    end: float
    parent: int | None  # id of the span open around this one
    request: int | None


class Compile(NamedTuple):
    event: str  # "jaxpr_trace", "jaxpr_to_mlir_module" or "backend_compile"
    seconds: float
    fun_name: str
    end: float  # time.perf_counter seconds


_spans: collections.deque[Span] = collections.deque(maxlen=MAX_RECORDS)
_compiles: collections.deque[Compile] = collections.deque(maxlen=MAX_RECORDS)
_counts: collections.Counter[str] = collections.Counter()
_ids = itertools.count()
_open = threading.local()  # .stack: the spans open on this thread
_enabled = jax.profiler.TraceAnnotation.is_enabled


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _On:
    __slots__ = ("name", "request", "id", "parent", "start", "ann")

    def __init__(self, name: str, request: int | None):
        self.name = name
        self.request = request

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        outer = stack[-1] if stack else None
        self.parent = None if outer is None else outer.id
        if self.request is None and outer is not None:
            self.request = outer.request
        self.id = next(_ids)
        stack.append(self)
        if self.request is None:
            self.ann = jax.profiler.TraceAnnotation(self.name)
        else:
            self.ann = jax.profiler.TraceAnnotation(self.name, request=self.request)
        self.ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.ann.__exit__(*exc)
        _open.stack.pop()
        _spans.append(Span(self.id, self.name, self.start, end, self.parent,
                           self.request))
        return False


def span(name: str, request: int | None = None):
    """A context that records span ``name`` while a profiler session is
    active, and does nothing otherwise.  Its value (``with span(n) as s``)
    is ``None`` when nothing is recorded, so that work done only to split
    one span from the next can be skipped."""
    if not _enabled():
        return _OFF
    return _On(name, request)


def records() -> list[Span]:
    """Every span kept, in the order they opened."""
    return sorted(_spans)


def compiles() -> list[Compile]:
    """Every compile event kept, in the order they ended."""
    return list(_compiles)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _counts[name] += n


def counts() -> dict[str, int]:
    """Every counter's value so far."""
    return dict(_counts)


def clear() -> None:
    """Forget every span, compile event and count kept so far."""
    _spans.clear()
    _compiles.clear()
    _counts.clear()


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is not None:
        _compiles.append(Compile(kind, float(duration_secs),
                                 str(kwargs.get("fun_name", "")),
                                 time.perf_counter()))


def _register() -> None:
    """Listen for compile events once per process: a listener left by an
    earlier import of this module (a reload) is replaced, not doubled."""
    for f in _monitoring.get_event_duration_listeners():
        if (getattr(f, "__module__", None) == __name__
                and getattr(f, "__qualname__", None) == _on_duration.__qualname__):
            jax.monitoring.unregister_event_duration_listener(f)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


_register()
