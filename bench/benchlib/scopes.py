"""Device time by program scope: which phase of the federated round ran
each operation of a trace.

A device trace names operations (``fusion.417``), not the scopes they
were traced under.  The program keeps its scanned round's optimized HLO
text (``repro.telemetry.trace.program_text("scan_segment")``, with
``REPRO_TRACE=1``), whose metadata does: an instruction's
``op_name`` reads ``jit(scan_segment)/while/body/closed_call/select/
cluster/.../add``.  Its scope path is the ``jax.named_scope`` names
after the scan body's prefix (``select/cluster``): JAX's own structure
(``while``, ``body``, ``cond``, ``branch_1_fun``, ``closed_call``,
transforms such as ``jit(...)``, Python qualified names) and the last
part, the primitive, are dropped.

An instruction without such metadata takes the scope of the loop,
branch or call that runs its computation; a fusion without metadata
takes its fused root's.  Operation names are unique only within one
program, so time is counted only inside the ``jit_scan_segment``
program's intervals on the device.

A loop, branch or call (``tracefile.CONTAINERS``) is an event of its own
on the device's operations line, and holds the events of what it runs.
Between those the device is still busy with the container itself (a
loop's trip: its condition, the hand-over to the next iteration), so
each event counts its own time, its length less that of the events
directly inside it, under its own scope.  A loop of many small
iterations spends much of its time so: Ward's merge loop at N = 2000
about half.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from benchlib.tracefile import Event

PROGRAM = "scan_segment"
MODULE = "jit_scan_segment"
PREFIX = f"jit({PROGRAM})/while/body/"

#: instructions that run no device operation of their own
FREE = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast"})

_STRUCTURE = frozenset({"closed_call", "while", "body", "cond"})
_BRANCH = re.compile(r"^branch_\d+_fun$")
_HEADER = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def scope_path(op_name: str) -> str:
    """``jit(scan_segment)/while/body/closed_call/select/cond/
    branch_0_fun/cluster/while/body/dynamic_slice`` -> ``select/cluster``;
    "" outside the scan body or where no named scope holds the op."""
    if not op_name.startswith(PREFIX):
        return ""
    parts = op_name[len(PREFIX):].split("/")[:-1]
    return "/".join(p for p in parts if p not in _STRUCTURE
                    and not _BRANCH.match(p) and "(" not in p
                    and "<" not in p)


class _Instr:
    __slots__ = ("name", "opcode", "op_name", "callees", "fused")

    def __init__(self, name, opcode, op_name):
        self.name, self.opcode, self.op_name = name, opcode, op_name
        self.callees: List[str] = []    # loops, branches, calls
        self.fused: Optional[str] = None  # a fusion's computation


def _parse(text: str) -> Tuple[Dict[str, List[_Instr]], str]:
    """(computation -> instructions, entry computation's name); a fusion
    without metadata is given its fused root's."""
    comps: Dict[str, List[_Instr]] = {}
    roots: Dict[str, str] = {}
    entry, current = "", None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            current = h.group(1)
            comps[current] = []
            if line.startswith("ENTRY "):
                entry = current
            continue
        m = _INSTR.match(line) if current is not None else None
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(" " + rest)
        meta = _OP_NAME.search(rest)
        ins = _Instr(m.group(1), op.group(1) if op else "",
                     meta.group(1) if meta else "")
        for kind, callee in _CALLEE.findall(rest):
            if kind == "calls" and ins.opcode == "fusion":
                ins.fused = callee
            elif kind != "to_apply" or ins.opcode == "call":
                ins.callees.append(callee)
        b = _BRANCHES.search(rest)
        if b:
            ins.callees += [c.strip().lstrip("%")
                            for c in b.group(1).split(",")]
        comps[current].append(ins)
        if line.lstrip().startswith("ROOT "):
            roots[current] = ins.op_name
    for instrs in comps.values():
        for ins in instrs:
            if not ins.op_name and ins.fused:
                ins.op_name = roots.get(ins.fused, "")
    return comps, entry


def op_scopes(text: str) -> Dict[str, str]:
    """{instruction name: scope path} for every instruction of the
    program that runs on the device as an operation or a container of
    its own: those of the entry computation and of the loops, branches
    and calls under it, not those inside fusions.  Path "" is
    unscoped."""
    comps, entry = _parse(text)
    out: Dict[str, str] = {}
    seen = set()

    def walk(comp: str, inherited: str) -> None:
        if comp in seen or comp not in comps:
            return
        seen.add(comp)
        for ins in comps[comp]:
            path = scope_path(ins.op_name) or inherited
            if ins.opcode not in FREE:
                out[ins.name] = path
            for callee in ins.callees:
                walk(callee, path)

    walk(entry, "")
    return out


def self_times(events) -> List[Tuple[object, float]]:
    """(event, its own time) for the events of one device line, which
    nest: an event's length less that of the events directly inside
    it.  The own times sum to the union of the events' intervals."""
    out: List[list] = []
    stack: List[int] = []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and out[stack[-1]][0].end <= e.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(e.end, parent[0].end) - e.start
        out.append([e, e.dur])
        stack.append(len(out) - 1)
    return [(e, t) for e, t in out]


def scope_seconds(trace, scopes: Dict[str, str],
                  window: Tuple[float, float]) -> Dict[str, float]:
    """{scope path: device seconds} of the events that ran inside the
    ``jit_scan_segment`` program's intervals in ``window``, each its own
    time (``self_times``), averaged over devices.  A name the table
    lacks counts as unscoped."""
    lo, hi = window
    totals: Dict[str, float] = {}
    n_dev = max(1, len(trace.ops))
    for dev, ops in enumerate(trace.ops):
        mods = sorted((e.start, e.start + e.dur) for e in trace.modules[dev]
                      if (e.name == MODULE or e.name.startswith(MODULE + "("))
                      and e.start + e.dur > lo and e.start < hi)
        starts = [a for a, _ in mods]
        inside = []
        for e in ops:
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start + e.dur > mods[i][1]:
                continue
            a, b = max(e.start, lo), min(e.start + e.dur, hi)
            if b > a:
                inside.append(Event(e.name, a, b - a))
        for e, t in self_times(inside):
            path = scopes.get(e.name, "")
            totals[path] = totals.get(path, 0.0) + t
    return {k: v * 1e-9 / n_dev for k, v in totals.items()}


def program_scopes() -> Optional[Dict[str, str]]:
    """The scanned round's scope table from the program, or None where
    the program keeps no text (untraced, or a program without it)."""
    try:
        from repro.telemetry import trace as program_trace
        text = program_trace.program_text(PROGRAM)
    except (ImportError, AttributeError):
        return None
    return op_scopes(text) if text else None


def _scope_times(rec: dict) -> Optional[Dict[str, float]]:
    """The traced call's device seconds by scope path, kept in ``rec``
    for the next reader; None without the program's scope table."""
    if "scope_s" not in rec:
        scopes = program_scopes()
        tr = rec["trace"]
        rec["scope_s"] = (None if scopes is None
                          else scope_seconds(tr, scopes, tr.window))
    return rec["scope_s"]


def round_device_ms(rec: dict, top: str) -> Optional[float]:
    """Device ms per round of the operations under top-level scope
    ``top`` in the traced call; None without the program's scope table
    or its program in the trace."""
    times, rounds = _scope_times(rec), len(rec["ids"])
    if not times or rounds == 0:
        return None
    t = sum(s for path, s in times.items()
            if path == top or path.startswith(top + "/"))
    return t / rounds * 1e3


def unscoped_share(rec: dict) -> Optional[float]:
    """Percent of the scanned program's device time in the traced call
    that falls under no named scope; None as ``round_device_ms``."""
    times = _scope_times(rec)
    total = sum(times.values()) if times else 0.0
    if total <= 0.0:
        return None
    return 100.0 * times.get("", 0.0) / total
