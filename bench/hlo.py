"""Trip-count-weighted opcode counts of a compiled XLA program's text.

Copied from the program's HLO analysis (``op_histogram`` and the parser
under it), so that the counts the benchmark reports are computed the same
way whatever the program later keeps or deletes.  A ``while`` body's ops
count once per trip; the trip count is the constant the loop condition
compares its counter against.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

# "  %name = SHAPE opcode(operands...), attrs" (the shape may be a tuple)
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[\w\[\]{},:()]+?)\s+([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")


@dataclass
class Op:
    name: str
    opcode: str
    rest: str  # operand list + attributes


@dataclass
class Computation:
    name: str
    ops: list[Op] = field(default_factory=list)


def parse_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in hlo.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                comps["__entry__"] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            cur.ops.append(Op(m.group(1), m.group(3), m.group(4)))
    return comps


def _called(rest: str) -> list[tuple[str, str]]:
    """(kind, computation) pairs named by calls=/to_apply=/condition=/body=
    and branch_computations={...}."""
    out = []
    for key in ("calls", "to_apply", "condition", "body"):
        for m in re.finditer(key + r"=%?([\w.\-]+)", rest):
            out.append((key, m.group(1)))
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", rest):
        out += [("branch", n.strip().lstrip("%")) for n in m.group(1).split(",")]
    return out


def _operand_names(rest: str) -> list[str]:
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return re.findall(r"%([\w.\-]+)", rest[:end])


def trip_count(cond: Computation) -> int:
    """Loop conditions compare the induction variable with a constant."""
    consts: dict[str, int] = {}
    for op in cond.ops:
        if op.opcode == "constant":
            m = re.match(r"\s*(-?\d+)\)", op.rest)
            if m:
                consts[op.name] = int(m.group(1))
    for op in cond.ops:
        if op.opcode == "compare":
            for name in _operand_names(op.rest):
                if name in consts:
                    return max(consts[name], 1)
    return max(consts.values(), default=1)


def op_histogram(hlo: str) -> dict[str, float]:
    """Opcode counts of everything reachable from ENTRY, each ``while``
    body and condition multiplied by its trip count.  Call-like ops count
    themselves and their callees; a ``conditional`` counts every branch."""
    comps = parse_computations(hlo)
    if "__entry__" not in comps:
        raise ValueError("no ENTRY computation found")
    memo: dict[str, dict[str, float]] = {}

    def walk(comp: Computation) -> dict[str, float]:
        if comp.name in memo:
            return memo[comp.name]
        memo[comp.name] = {}  # cycle guard
        h: dict[str, float] = {}

        def bump(d: dict[str, float], mult: float = 1.0) -> None:
            for k, v in d.items():
                h[k] = h.get(k, 0.0) + v * mult

        for op in comp.ops:
            called = _called(op.rest)
            h[op.opcode] = h.get(op.opcode, 0.0) + 1.0
            if op.opcode == "while":
                named = dict(called)
                body, cond = comps.get(named.get("body", "")), comps.get(
                    named.get("condition", ""))
                trips = trip_count(cond) if cond else 1
                for sub in (body, cond):
                    if sub is not None:
                        bump(walk(sub), trips)
                continue
            for _, name in called:
                if name in comps:
                    bump(walk(comps[name]))
        memo[comp.name] = h
        return h

    return walk(comps["__entry__"])


def scan_trips(hlo: str) -> int:
    """Trips of the longest outermost ``while`` loop (one inside no other
    loop): a sweep executor's event scan.  The random draws' own short
    loops sit beside it and are not counted."""
    comps = parse_computations(hlo)
    if "__entry__" not in comps:
        raise ValueError("no ENTRY computation found")
    seen: set[str] = set()

    def walk(comp: Computation) -> int:
        if comp.name in seen:
            return 0
        seen.add(comp.name)
        most = 0
        for op in comp.ops:
            called = _called(op.rest)
            if op.opcode == "while":
                cond = comps.get(dict(called).get("condition", ""))
                most = max(most, trip_count(cond) if cond else 1)
                continue
            most = max([most] + [walk(comps[n]) for _, n in called if n in comps])
        return most

    return walk(comps["__entry__"])


def fusion_roots(hlo: str) -> dict[str, str]:
    """``{fusion op name: opcode at the root of what it computes}``,
    following nested fusions down (``fusion.124`` -> ``scatter``)."""
    comps = parse_computations(hlo)

    def root(name: str, depth: int = 0) -> str | None:
        comp = comps.get(name)
        if comp is None or not comp.ops or depth > 8:
            return None
        last = comp.ops[-1]  # ROOT is printed last
        if last.opcode == "fusion":
            called = dict(_called(last.rest)).get("calls")
            return root(called, depth + 1) if called else None
        return last.opcode

    out = {}
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "fusion":
                called = dict(_called(op.rest)).get("calls")
                r = root(called) if called else None
                if r is not None:
                    out[op.name] = r
    return out


def trips_per_job(execs) -> float:
    """Event-scan trips per simulated job over ``(compiled text, lanes,
    jobs per lane)`` executors: each executor's trips times its lanes, over
    the jobs those lanes simulate."""
    trips = sum(scan_trips(text) * lanes for text, lanes, _ in execs)
    return trips / sum(lanes * jobs for _, lanes, jobs in execs)


def sorts_per_job(execs) -> float:
    """``sort`` ops per simulated job over the same executors, weighted by
    loop trips and times the lanes (a batched sort sorts every lane once)."""
    sorts = sum(op_histogram(text).get("sort", 0.0) * lanes for text, lanes, _ in execs)
    return sorts / sum(lanes * jobs for _, lanes, jobs in execs)
