"""Profiler trace of a stretch of the window, reduced to device busy time.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per executed operation (a loop's body ops once
per trip); host threads are planes ``/host:...`` whose events include the
``TraceAnnotation`` spans of the program and of this benchmark.  All share
one clock.

The reduction works on plain tuples, so that a test can feed it a small
recorded trace:

- busy time of a chip: the union of its op intervals inside the window;
- idle share: 1 - busy / window, averaged over the chips;
- top device ops by total time, named by the HLO instruction (with what a
  fusion computes, where the entry can tell from the compiled text); a
  loop or call op, whose time is its body's, is left out of this list;
- idle time, summed by the innermost known host span open at each instant
  of it (``outside spans`` where none is): what the host was doing while
  the chip waited.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_CONTAINERS = ("while", "conditional", "call")
_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclass
class Trace:
    """What a trace holds for the reduction.

    ``ops[device]``: ``(name, start_ns, dur_ns)`` of every device op;
    ``spans``: ``(name, start_ns, dur_ns)`` of every host event.
    """

    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    spans: list[tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over chips
    idle_share: float
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.ops[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans += [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events if not e.name.startswith("$")]
    return out


class capture:
    """``with capture(dir) as cap:`` traces the block; ``cap.trace`` then
    holds the loaded :class:`Trace`, and ``dir`` is removed."""

    def __init__(self, directory: str):
        self.dir = directory
        self.trace: Trace | None = None

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # keep Python calls out: they slow the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        if exc[0] is None:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
            self.trace = load(found[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return False


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_segments(spans, w0, w1):
    """Cut ``[w0, w1]`` where a span opens or closes: ``(starts, labels)``,
    each segment labelled by the innermost span open in it.  Spans of one
    thread nest, so the innermost is the latest opened and not yet closed."""
    marks = []
    for i, (s, e, _) in enumerate(spans):
        marks += [(max(s, w0), 1, i), (min(e, w1), 0, i)]
    marks.sort()
    open_: list[int] = []
    starts, labels = [w0], ["outside spans"]
    for t, kind, i in marks:
        if kind:
            open_.append(i)
        elif i in open_:
            open_.remove(i)
        label = spans[open_[-1]][2] if open_ else "outside spans"
        if t > starts[-1]:
            starts.append(t)
            labels.append(label)
        else:
            labels[-1] = label
    return starts, labels


def reduce(trace: Trace, *, known_spans: tuple[str, ...], top: int = 10,
           labels: dict[str, str] | None = None) -> Reduced | None:
    """Busy and idle time inside the ``bench.window`` span of ``trace``;
    ``None`` when the trace holds no device ops (no TPU plane).
    ``labels`` renames device ops in the list of top ops."""
    if not trace.ops:
        return None
    windows = [(s, s + d) for n, s, d in trace.spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]
    busy, per_op = [], {}
    idle_by: dict[str, float] = {}
    starts, seg_labels = _host_segments(
        [(s, s + d, n) for n, s, d in trace.spans
         if n in known_spans and s + d > w0 and s < w1], w0, w1)
    for ops in trace.ops.values():
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                key = op_name(name)
                if not key.startswith(_CONTAINERS):
                    per_op[key] = per_op.get(key, 0.0) + (b - a)
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            j = bisect.bisect_right(starts, a) - 1
            while a < b:
                end = starts[j + 1] if j + 1 < len(starts) else w1
                cut = min(b, end)
                if cut > a:
                    idle_by[seg_labels[j]] = idle_by.get(seg_labels[j], 0.0) + (cut - a)
                a = cut
                j += 1
    n = len(trace.ops)
    window = w1 - w0
    busy_mean = sum(busy) / n
    labels = labels or {}
    ranked = [(labels.get(k, k), v) for k, v in
              sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=window * 1e-9,
        busy_s=busy_mean * 1e-9,
        idle_share=1.0 - busy_mean / window,
        device_ops=[(k, v * 1e-9 / n) for k, v in ranked],
        idle_gaps=[(k, v * 1e-9 / n) for k, v in gaps],
    )
