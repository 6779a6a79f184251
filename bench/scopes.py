"""Device time by the program's ``ppf.*`` scopes.

A TPU trace names each operation by its HLO instruction and carries no
metadata, so the scope of an operation comes from the compiled program's
text: ``scope_map`` maps each instruction to the innermost ``ppf.*`` scope
of its ``op_name``.  An instruction with no scope of its own takes its
fused computation's (a fusion) or the scope of the instruction that calls
its computation (a loop body takes its loop's).  ``per_step`` sums the
traced device time of one scope's operations over the decode steps the
trace holds, counting the steps by the operations of one scope that runs
once a step.
"""
from __future__ import annotations

import collections
import re

SCOPE = re.compile(r"ppf\.[a-z]+(?:\.[a-z]+)?")
HEADER = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(")
INSTR = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# operations whose trace event spans the operations of the computations
# they call: their time is counted through those
CONTAINERS = {"while", "call", "conditional", "async-start", "async-done"}


def _parse(text: str):
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = HEADER.match(line)
        if head:
            current = head.group(2)
            comps[current] = {"instrs": [], "root": None}
            if head.group(1):
                entry = current
            continue
        m = INSTR.match(line) if current else None
        if not m:
            continue
        name, rest = m.group(2), m.group(3)
        op = OPCODE.search(" " + rest)
        called = CALLED.findall(rest)
        for group in BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        scopes = SCOPE.findall(" ".join(OP_NAME.findall(rest)))
        comps[current]["instrs"].append(
            (name, op.group(1) if op else "", scopes[-1] if scopes else None,
             called))
        if m.group(1):
            comps[current]["root"] = name
    return comps, entry


def scope_map(text: str) -> tuple[dict, list]:
    """``({instruction: scope}, [container instructions])`` of compiled
    HLO ``text``."""
    comps, entry = _parse(text)

    def own(comp):
        found = collections.Counter()
        for name, _, scope, _ in comps.get(comp, {"instrs": []})["instrs"]:
            if scope and name == comps[comp]["root"]:
                return scope
            if scope:
                found[scope] += 1
        return found.most_common(1)[0][0] if found else None

    out, loops, seen = {}, [], {entry}
    stack = [(entry, None)]
    while stack:
        comp, inherited = stack.pop()
        for name, op, scope, called in comps[comp]["instrs"]:
            if scope is None and op == "fusion" and called:
                scope = own(called[0])
            scope = scope or inherited
            if scope:
                out[name] = scope
            if op in CONTAINERS:
                loops.append(name)
            for c in called:
                if c in comps and c not in seen:
                    seen.add(c)
                    stack.append((c, scope))
    return out, loops


def module_name(text: str) -> str:
    """The ``HloModule`` name of compiled HLO ``text``."""
    return text.split(None, 2)[1].rstrip(",")


def instruction(event_name: str) -> str:
    """The HLO instruction an operation event of the trace names."""
    return re.split(r"[\s=]", event_name.strip().lstrip("%"), 1)[0]


def per_step(ctx: dict, scope: str, anchor: str) -> float | None:
    """Device seconds of ``scope``'s operations per decode step in the
    traced window, the steps counted by the most frequent operation of
    ``anchor`` (a scope that runs once a step); ``None`` where the window
    has no scope map or no step of the program was traced.  Operations
    are taken inside the runs of the window's program (the trace's
    module events named as its ``HloModule``), where the trace has any."""
    c = ctx["window"].counters
    if "scope_of" not in c:
        return None
    scope_of, loops, module = c["scope_of"], set(c["loops"]), c["module"]
    lo, hi = ctx["lo"], ctx["hi"]
    total, steps = 0.0, collections.Counter()
    devices = list(ctx["busy"].values())[:1]
    for events in devices:
        runs = [(a, b) for a, b, name in events if name.startswith(module)]
        for a, b, name in events:
            inst = instruction(name)
            if inst not in scope_of or inst in loops or not lo <= a <= hi:
                continue
            if runs and not any(s <= a and b <= e for s, e in runs):
                continue
            if scope_of[inst] == scope:
                total += b - a
            if scope_of[inst] == anchor:
                steps[inst] += 1
    if not steps:
        return None
    return total * 1e-9 / max(steps.values())
