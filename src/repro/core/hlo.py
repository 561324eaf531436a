"""Trip-count-weighted opcode histogram of compiled HLO text.

``compiled.cost_analysis()`` counts each ``while`` body once, but the
engine's event loop is a ``lax.scan``: a per-event sort inside a 2M-trip
loop runs 2M times.  :func:`op_histogram` parses the HLO text, walks the
call graph from ENTRY, and multiplies every while body and condition by its
trip count (recovered from the loop condition's comparison constant), so
"how many sorts does one event step pay?" can be read off a compiled
executor (``benchmarks/profile_engine.py``, ``tests/test_alloc_fused.py``).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

# "  %name = SHAPE opcode(operands...), attrs" (shape may be a tuple)
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[\w\[\]{},]+)\s+([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")


@dataclass
class Op:
    name: str
    shape_str: str
    opcode: str
    rest: str  # operand list + attributes (raw tail of the line)


@dataclass
class Computation:
    name: str
    ops: list[Op] = field(default_factory=list)


def parse_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry_name = None
    for line in hlo.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and ("->" in line):
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry_name = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            cur.ops.append(Op(m.group(1), m.group(2), m.group(3), m.group(4)))
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def _called_comps(rest: str) -> list[tuple[str, str]]:
    """computation names referenced via calls=/to_apply=/condition=/body=."""
    out = []
    for key in ("calls=", "to_apply=", "condition=", "body="):
        for m in re.finditer(re.escape(key) + r"%?([\w.\-]+)", rest):
            out.append((key[:-1], m.group(1)))
    return out


def _operand_names(rest: str) -> list[str]:
    """Names inside the top-level parens of 'opcode(...), attrs'."""
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


def _trip_count(cond: Computation) -> int:
    """Loop conditions compare the induction var against a constant bound."""
    consts: dict[str, int] = {}
    for op in cond.ops:
        if op.opcode == "constant":
            m = re.search(r"constant\((\d+)\)", "constant(" + op.rest)
            if m:
                consts[op.name] = int(m.group(1))
    for op in cond.ops:
        if op.opcode == "compare":
            for name in _operand_names(op.rest):
                if name in consts:
                    return max(consts[name], 1)
    if consts:
        return max(consts.values())
    return 1


def op_histogram(hlo: str) -> dict[str, float]:
    """Trip-count-weighted opcode histogram of the ENTRY call graph.

    Counts every op reachable from ENTRY, multiplying while bodies/conds by
    their trip count — which is what
    makes "how many ``sort``s does one event step pay?" answerable from a
    compiled scan: a per-event sort inside a 2M-trip loop shows up 2M
    times, not once.  Call-like ops (``fusion``, ``call``, ``reduce``,
    ``conditional``) count themselves AND their subcomputations' ops;
    ``conditional`` counts every branch (an upper bound — branches are
    traced, not taken).
    """
    comps = parse_computations(hlo)
    entry = comps.get("__entry__")
    if entry is None:
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
            called = dict(_called_comps(op.rest))
            if op.opcode == "while":
                body = comps.get(called.get("body", ""))
                cond = comps.get(called.get("condition", ""))
                trips = _trip_count(cond) if cond else 1
                h["while"] = h.get("while", 0.0) + 1.0
                if body:
                    bump(walk(body), trips)
                if cond:
                    bump(walk(cond), trips)
                continue
            h[op.opcode] = h.get(op.opcode, 0.0) + 1.0
            for _, sub_name in _called_comps(op.rest):
                sub = comps.get(sub_name)
                if sub is not None:
                    bump(walk(sub))

        memo[comp.name] = h
        return h

    return walk(entry)
