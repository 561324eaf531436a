"""The per-lane width of the job state an event scan carries, read from a
compiled program's text.

A sweep executor's event scan is its longest outermost ``while`` loop (as
``bench/hlo.py`` finds it).  Its carried tuple holds what each trip
updates, the per-job state among it, beside what every trip only reads:
the tape a pooled scan admits jobs from, an index vector.  An element is
read-only where the loop body's root hands back the body parameter's own
element at the same index.  The width is the largest trailing dimension
of an updated element of rank 2 or more: ``[lanes, jobs]`` on a full
tape, ``[lanes, slots]`` in a slot pool.
"""

from __future__ import annotations

import re

from bench import hlo

_WHILE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(.*\))\s+while\(")
_ARRAY = re.compile(r"^\w+\[([\d,]*)\]")
_INDEX = re.compile(r"index=(\d+)")


def _elements(shape: str) -> list[str]:
    """The top-level elements of a tuple shape string ``(a, b, ...)``."""
    inner = re.sub(r"/\*index=\d+\*/", "", shape.strip()[1:-1])
    out, depth, cur = [], 0, ""
    for ch in inner:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append(cur.strip())
            cur = ""
        else:
            cur += ch
    if cur.strip():
        out.append(cur.strip())
    return out


def _loops(text: str):
    """``(trips, tuple shape, body name)`` of every ``while`` reachable from
    ENTRY without entering another loop."""
    comps = hlo.parse_computations(text)
    shapes = {}
    for line in text.splitlines():
        m = _WHILE.match(line)
        if m:
            name = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)", line).group(1)
            shapes[name] = m.group(1)
    seen: set[str] = set()
    found = []

    def walk(comp) -> None:
        if comp.name in seen:
            return
        seen.add(comp.name)
        for op in comp.ops:
            called = dict(hlo._called(op.rest))
            if op.opcode == "while":
                cond = comps.get(called.get("condition", ""))
                trips = hlo.trip_count(cond) if cond else 1
                if op.name in shapes:
                    found.append((trips, shapes[op.name], called.get("body")))
                continue
            for _, name in hlo._called(op.rest):
                if name in comps:
                    walk(comps[name])

    if "__entry__" in comps:
        walk(comps["__entry__"])
    return comps, found


def scan_width(text: str) -> int | None:
    """Per-lane width of the state the event scan of ``text`` updates, or
    None where no loop carries an updated array of rank 2 or more."""
    comps, loops = _loops(text)
    if not loops:
        return None
    _, shape, body_name = max(loops, key=lambda lp: lp[0])
    body = comps.get(body_name)
    if body is None or not body.ops or body.ops[-1].opcode != "tuple":
        return None
    params = {op.name for op in body.ops if op.opcode == "parameter"}
    passed = {}  # get-tuple-element of the body parameter -> its index
    for op in body.ops:
        if op.opcode == "get-tuple-element":
            names = hlo._operand_names(op.rest)
            m = _INDEX.search(op.rest)
            if names and names[0] in params and m:
                passed[op.name] = int(m.group(1))
    widths = []
    root = hlo._operand_names(body.ops[-1].rest)
    for i, (elem, src) in enumerate(zip(_elements(shape), root, strict=False)):
        if passed.get(src) == i:
            continue  # read, never written: the tape, an index vector
        m = _ARRAY.match(elem)
        dims = [int(d) for d in m.group(1).split(",") if d] if m else []
        if len(dims) >= 2:
            widths.append(dims[-1])
    return max(widths) if widths else None


def slots_per_lane(ctx) -> int | None:
    """The widest per-lane job state over the executors the window ran
    (``ctx.entry.executors()``: compiled text, lanes, jobs), or None where
    none is found.  Read only from a run that captured a device trace, as
    ``bench/program.py``'s readers are."""
    if ctx.trace is None:
        return None
    widths = [w for text, _, _ in ctx.entry.executors()
              if (w := scan_width(text)) is not None]
    return max(widths) if widths else None
