"""Just enough of a parser for compiled (post-SPMD) HLO text to walk its
collective schedule (:func:`repro.analyze.collectives.collective_schedule_from_hlo`).

* **computations** are split on header lines (``%name (...) -> ... {``)
  and end at a lone ``}``; the entry is the one whose header starts with
  ``ENTRY``;
* an op's **opcode** is the token right before its first ``(`` after the
  result shape;
* **calls**: ``call`` / ``conditional`` / ``async-start`` targets and
  ``fusion`` bodies are recorded per computation, and every ``while`` as
  ``(condition, body, trip)``, the trip count taken from the
  backend_config ``known_trip_count`` that XLA's loop simplifier sets
  (0 when it could not prove one);
* a collective's **replica group size** comes from its
  ``replica_groups``, explicit (``{{0,1},{2,3}}``) or iota
  (``[G,N]<=[...]``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["COLLECTIVES", "Computation", "Op", "group_size",
           "parse_module"]

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,}{]+)\}\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,()TS]+)\]")
_TRIP_RE = re.compile(r'known_trip_count[^}]*"n"\s*:\s*"(\d+)"')


@dataclasses.dataclass
class Op:
    opcode: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    whiles: List[Tuple[str, str, float]]     # (cond, body, trip)
    calls: List[str]                         # call/conditional edges
    fusion_calls: List[str]                  # fusion-called computations


def _first_word(rest: str) -> str:
    """Opcode of an op line: the token right before the first '(' that is
    not part of the result-shape text."""
    m = re.match(r"^(?:\([^()]*\)|[a-z]+\d*[a-z\d]*\[[\d,]*\](?:\{[\d,]*\})?"
                 r"|\s|,|/\*[^*]*\*/)*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if cur is None:
            if "->" in line and line.rstrip().endswith("{"):
                m = _COMP_RE.match(line.strip())
                if m:
                    cur = m.group(1)
                    comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        comps[cur].append(line)
    return comps


def _parse_computation(name: str, lines: List[str]) -> Computation:
    comp = Computation(name=name, ops=[], whiles=[], calls=[],
                       fusion_calls=[])
    for line in lines:
        dm = _DEF_RE.match(line)
        if not dm:
            continue
        opcode = _first_word(dm.group(2))
        if not opcode:
            continue
        comp.ops.append(Op(opcode=opcode, line=line))
        if opcode == "while":
            cm = re.search(r"condition=%?([\w.\-]+)", line)
            bm = re.search(r"body=%?([\w.\-]+)", line)
            tm = _TRIP_RE.search(line)
            trip = float(tm.group(1)) if tm else 0.0
            if cm and bm:
                comp.whiles.append((cm.group(1), bm.group(1), trip))
        elif opcode in ("call", "conditional", "async-start"):
            for cm in re.finditer(
                    r"(?:to_apply|branch_computations|called_computation)="
                    r"\{?%?([\w.\-]+)", line):
                comp.calls.append(cm.group(1))
        elif opcode == "fusion":
            cm = re.search(r"calls=%?([\w.\-]+)", line)
            if cm:
                comp.fusion_calls.append(cm.group(1))
    return comp


def parse_module(hlo: str) -> Tuple[Optional[str], Dict[str, Computation]]:
    """``(entry name, computations by name)`` of an HLO module's text.
    Without an ``ENTRY`` header the first computation stands in for it."""
    parsed = {name: _parse_computation(name, lines)
              for name, lines in _split_computations(hlo).items()}
    entry: Optional[str] = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            match = re.search(r"ENTRY\s+%?([\w.\-]+)", line)
            if match:
                entry = match.group(1)
            break
    if entry is None and parsed:
        entry = next(iter(parsed))
    return entry, parsed


def group_size(line: str) -> int:
    """Replica group size of a collective op line (1 without groups)."""
    gm = _GROUPS_RE.search(line)
    if gm:
        first = gm.group(1).split("},{")[0]
        return len([x for x in first.strip("{}").split(",") if x])
    gi = _GROUPS_IOTA_RE.search(line)
    if gi:
        return int(gi.group(2))
    return 1
