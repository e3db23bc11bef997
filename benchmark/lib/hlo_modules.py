"""The programs a ``jax.profiler`` trace embeds, ONE instruction table a
program.

``lib/trace.py`` labels an ``XLA Ops`` event by its instruction's name alone,
over every program of the trace.  Two programs of one function (a serving
tick at 0 and at 64 prompt rows) number their fusions alike and fill them
differently: ``fusion.259`` is the QKV projection in one and a squeeze in
the other, and the one label both get is wrong in one of them.  A reader
that has to tell a projection from the copies around it, in every program,
reads the tables here instead: for each program, by the name its ``XLA
Modules`` events carry (``jit_tick(<program id>)``), every instruction's
``op_name`` (the jax scope path) and whether it IS a matrix product: a
``dot`` or ``convolution``, or a fusion (or call) whose body holds one,
whatever the fusion's root is named.

Where it is read from (one v5e trace, by hand, PR 63): the plane
``/host:metadata`` holds an event metadata entry a program, named as the
module events are, with the program's ``HloProto`` as a bytes stat.  Field
numbers are xplane.proto's and hlo.proto's; what does not parse is left out.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmark.lib.trace import _fields, _varint

METADATA_PLANE = "/host:metadata"
PRODUCTS = ("dot", "convolution")


class Instruction(NamedTuple):
    opcode: str
    op_name: str
    product: bool       # a dot or convolution, or a body that holds one


def _subs(buf: bytes, field: int) -> List[bytes]:
    return [v for f, wt, v in _fields(buf) if f == field and wt == 2]


def _ints(buf: bytes, field: int) -> List[int]:
    """A repeated int64 field, packed or not."""
    out: List[int] = []
    for f, wt, v in _fields(buf):
        if f == field and wt == 0:
            out.append(v)
        elif f == field and wt == 2:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _table(module: bytes) -> Dict[str, Instruction]:
    """HloModuleProto -> instruction name -> :class:`Instruction`."""
    bodies: Dict[int, List[Tuple[str, List[int]]]] = {}   # computation id
    rows: List[Tuple[str, str, str, List[int]]] = []
    for comp in _subs(module, 3):
        comp_id = next((v for f, wt, v in _fields(comp)
                        if f == 5 and wt == 0), None)
        body = bodies.setdefault(comp_id, [])
        for ins in _subs(comp, 2):
            name = opcode = op_name = ""
            for f, wt, v in _fields(ins):
                if wt != 2:
                    continue
                if f == 1:
                    name = v.decode(errors="replace")
                elif f == 2:
                    opcode = v.decode(errors="replace")
                elif f == 7:
                    op_name = next((x.decode(errors="replace")
                                    for x in _subs(v, 2)), "")
            calls = _ints(ins, 38)
            body.append((opcode, calls))
            rows.append((name, opcode, op_name, calls))

    holds: Dict[int, bool] = {}

    def product_in(comp_id: int) -> bool:
        if comp_id not in holds:
            holds[comp_id] = False          # a cycle reads as none
            holds[comp_id] = any(
                opcode in PRODUCTS or any(product_in(c) for c in calls)
                for opcode, calls in bodies.get(comp_id, ()))
        return holds[comp_id]

    return {name: Instruction(
                opcode, op_name,
                opcode in PRODUCTS or (opcode in ("fusion", "call") and any(
                    product_in(c) for c in calls)))
            for name, opcode, op_name, calls in rows}


def read(path: str) -> Dict[str, Dict[str, Instruction]]:
    """Program name (as its ``XLA Modules`` events carry it) -> its
    instruction table, for every program the trace at ``path`` embeds."""
    with open(path, "rb") as f:
        data = f.read()
    out: Dict[str, Dict[str, Instruction]] = {}
    try:
        for plane in _subs(data, 1):
            if METADATA_PLANE.encode() not in _subs(plane, 2):
                continue
            for entry in _subs(plane, 4):           # map<id, XEventMetadata>
                for meta in _subs(entry, 2):
                    names = _subs(meta, 2)
                    for stat in _subs(meta, 5):
                        for proto in _subs(stat, 6):    # bytes: an HloProto
                            for module in _subs(proto, 1):
                                table = _table(module)
                                if names and table:
                                    out[names[0].decode()] = table
    except (ValueError, IndexError):
        pass
    return out


class Attributed:
    """A device's ops, each looked up in the table of the program whose
    execution (an ``XLA Modules`` event) it started in."""

    def __init__(self, trace, tables: Dict[str, Dict[str, Instruction]]):
        dev = trace.devices[0]
        self.runs = sorted((s, e, name) for name, s, e in dev.modules)
        self._starts = [s for s, _, _ in self.runs]
        self.tables, self.ops = tables, dev.ops

    def run_of(self, op) -> Optional[Tuple[float, float, str]]:
        i = bisect.bisect_right(self._starts, op.start) - 1
        if i < 0 or op.start >= self.runs[i][1]:
            return None
        return self.runs[i]

    def instruction(self, op) -> Optional[Instruction]:
        run = self.run_of(op)
        return self.tables.get(run[2], {}).get(op.name) if run else None
