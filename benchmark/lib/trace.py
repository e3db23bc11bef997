"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to device busy
time, time by operation, idle gaps and collective exposure.

What one v5e trace looks like (read off by hand, PR 23): each chip is a plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
execution, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per
executed HLO instruction; the event's name is the instruction's whole text,
``%name = shape opcode(operands), ...``; a ``while`` or ``call`` event spans
its body's events), ``Async XLA Ops`` (copies and collectives in flight) and
``Steps``.  Event stats carry no scope, so the instruction -> ``op_name``
map (``jit(train_step)/optimizer/...``) is recovered from the HLO protos
the profiler embeds in the same file, by a small protobuf wire reader.

Definitions:

* busy      union of the ``XLA Ops`` intervals of a device inside the window
* window    first event start to last event end over all device planes
* self time an event's duration minus the events nested inside it
* idle gap  a stretch of the window with no op on the device
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
WAITS = re.compile(r"poll|acquire|wait|select|sleep|readinto|recv|<unknown> join")
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


class Op:
    __slots__ = ("name", "text", "start", "end", "self_ns", "op_name")

    def __init__(self, text: str, start: float, end: float):
        self.text = text
        m = re.match(r"%?([\w.\-]+)", text)
        self.name = m.group(1) if m else text
        self.start, self.end = start, end
        self.self_ns = end - start
        self.op_name = ""

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def is_collective(self) -> bool:
        return bool(COLLECTIVE.search(self.text))

    @property
    def is_pallas(self) -> bool:
        return PALLAS_CALL in self.text


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


# ---- protobuf wire reader (for the embedded HLO metadata only) ----------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7
        if shift > 63:
            raise ValueError("varint too long")


def _fields(buf: bytes):
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wt = key >> 3, key & 7
        if field == 0:
            raise ValueError("field 0")
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
            if len(v) != ln:
                raise ValueError("truncated")
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError("group wire type")
        if i > n:
            raise ValueError("overrun")
        yield field, wt, v


_NAME = re.compile(rb"^[\w.\-]{1,120}$")


def _instruction(buf: bytes) -> Optional[Tuple[str, str]]:
    """(name, op_name) when ``buf`` reads as an HloInstructionProto with
    metadata: field 1 name, 2 opcode, 7 metadata{2: op_name}."""
    name = opcode = meta = None
    try:
        for f, wt, v in _fields(buf):
            if wt != 2:
                continue
            if f == 1:
                name = v
            elif f == 2:
                opcode = v
            elif f == 7:
                meta = v
    except (ValueError, IndexError):
        return None
    if not (name and opcode and meta and _NAME.match(name)
            and _NAME.match(opcode)):
        return None
    try:
        for f, wt, v in _fields(meta):
            if f == 2 and wt == 2:
                return name.decode(), v.decode(errors="replace")
    except (ValueError, IndexError):
        pass
    return None


def hlo_op_names(path: str, limit_bytes: int = 64 << 20) -> Dict[str, str]:
    """instruction name -> ``op_name`` (the jax scope path) for every HLO
    module embedded in the trace.  Walks the file as nested protobuf
    messages; what does not parse is skipped."""
    with open(path, "rb") as f:
        data = f.read(limit_bytes)
    out: Dict[str, str] = {}

    def walk(buf: bytes, depth: int) -> None:
        if depth > 9 or len(buf) < 8:
            return
        try:
            subs = [v for _, wt, v in _fields(buf) if wt == 2 and len(v) >= 8]
        except (ValueError, IndexError):
            return
        for v in subs:
            if b"/" in v:
                hit = _instruction(v)
                if hit:
                    out.setdefault(*hit)
                    continue
            walk(v, depth + 1)

    walk(data, 0)
    return out


# ---- reduction -----------------------------------------------------------


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _self_times(ops: List[Op]) -> None:
    """Subtract nested events from their parents (one line, so events
    either nest or are disjoint)."""
    stack: List[Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end + 1:
            stack[-1].self_ns -= op.dur
        stack.append(op)


class Device:
    def __init__(self, index: int, ops: List[Op],
                 modules: List[Tuple[str, float, float]]):
        self.index, self.ops, self.modules = index, ops, modules
        _self_times(ops)
        self.busy = _union((o.start, o.end) for o in ops)

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)

    def span(self) -> Tuple[float, float]:
        return (self.busy[0][0], self.busy[-1][1]) if self.busy else (0.0, 0.0)


class Reduced:
    """What the per-layer readers get: ``devices`` (ops with self times
    and scope paths), and the sums the contract's ``device`` block wants."""

    def __init__(self, devices: List[Device], path: str = "",
                 host: Optional[List[Tuple[str, float, float]]] = None):
        self.devices, self.path = devices, path
        self.host = host or []   # (name, start_ns, end_ns) of host threads
        spans = [d.span() for d in devices if d.busy]
        self.t0 = min(s for s, _ in spans) if spans else 0.0
        self.t1 = max(e for _, e in spans) if spans else 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an op ran on the device, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def ops(self, pred=None) -> List[Op]:
        return [o for d in self.devices for o in d.ops
                if pred is None or pred(o)]

    def self_seconds(self, pred) -> float:
        """Self time of the ops ``pred`` picks, averaged over devices."""
        n = max(len(self.devices), 1)
        return sum(o.self_ns for o in self.ops(pred)) / n / 1e9

    def module_durations(self, pattern: str) -> List[float]:
        """Device seconds of each execution of the programs whose name
        matches ``pattern`` (device 0: one program spans every chip)."""
        rx = re.compile(pattern)
        if not self.devices:
            return []
        return [(e - s) / 1e9 for name, s, e in self.devices[0].modules
                if rx.search(name)]

    def full_runs(self, pattern: str) -> List[Tuple[float, float]]:
        """(start_ns, end_ns) of the executions of the matching program
        that the trace holds whole (a capture cuts the first and last)."""
        rx = re.compile(pattern)
        if not self.devices:
            return []
        runs = [(s, e) for name, s, e in self.devices[0].modules
                if rx.search(name)]
        if not runs:
            return []
        longest = max(e - s for s, e in runs)
        return [(s, e) for s, e in runs if e - s >= 0.9 * longest]

    def self_seconds_within(self, pred, spans: List[Tuple[float, float]]) -> float:
        """As ``self_seconds``, over the ops that start inside ``spans``
        (device 0's clock holds for all: one program spans the chips)."""
        n = max(len(self.devices), 1)
        total = 0.0
        for o in self.ops(pred):
            if any(s <= o.start < e for s, e in spans):
                total += o.self_ns
        return total / n / 1e9

    def top_ops(self, k: int = 10) -> List[List]:
        total: Dict[str, float] = {}
        n = max(len(self.devices), 1)
        for o in self.ops():
            key = o.name if not o.op_name else f"{o.name} [{_short(o.op_name)}]"
            total[key] = total.get(key, 0.0) + o.self_ns / n / 1e9
        return [[k_, v] for k_, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[Tuple[float, float]]:
        """The ``k`` longest (start_ns, seconds) stretches of device 0's
        window with no op running."""
        if not self.devices or not self.devices[0].busy:
            return []
        b = self.devices[0].busy
        gaps = [(b[i][1], (b[i + 1][0] - b[i][1]) / 1e9)
                for i in range(len(b) - 1)]
        return sorted(gaps, key=lambda g: -g[1])[:k]

    def host_label(self, start_ns: float, end_ns: float) -> str:
        """What the host was doing during a device gap: the longest host
        event (python tracer, runtime threads) that lies inside the gap
        and is not a wait.  The program's own spans are not on this clock
        yet (PERF.md, Open questions), so this names a function, not a
        phase."""
        best = None
        for name, s, e in self.host:
            if s >= start_ns and e <= end_ns and not WAITS.search(name) and (
                    best is None or e - s > best[1]):
                best = (name, e - s)
        if best is None:
            return "host threads waiting"
        return f"{best[0].lstrip('$')[:60]} ({best[1] / 1e6:.2f} ms of the gap)"

    def labelled_gaps(self, k: int = 10) -> List[List]:
        return [[self.host_label(s, s + sec * 1e9), sec]
                for s, sec in self.idle_gaps(k)]

    def collective_exposed_s(self) -> float:
        """Seconds, averaged over devices, in which a collective was
        running and no other op was (its time not hidden behind compute).
        Collectives show on the ops line as start/done pairs and as sync
        ops; an op is compute if it is neither a collective nor a
        container whose self time is zero."""
        total = 0.0
        for d in self.devices:
            coll = _union((o.start, o.end) for o in d.ops if o.is_collective)
            comp = _union((o.start, o.start + max(o.self_ns, 0.0))
                          for o in d.ops
                          if not o.is_collective and o.self_ns > 0)
            total += _minus(coll, comp)
        return total / max(len(self.devices), 1) / 1e9


def _minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of ``a`` not covered by ``b`` (both sorted unions)."""
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def _short(op_name: str, keep: int = 3) -> str:
    parts = [p for p in op_name.split("/") if p]
    return "/".join(parts[-keep:])


def reduce_profile(profile, op_names: Optional[Dict[str, str]] = None,
                   path: str = "") -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    devices = []
    host: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 20_000:   # 20 us: shorter spans no gap
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
        if not m:
            continue
        ops: List[Op] = []
        modules: List[Tuple[str, float, float]] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns, e.start_ns + e.duration_ns))
            elif line.name == MODULES_LINE:
                for e in line.events:
                    modules.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
        if op_names:
            for o in ops:
                o.op_name = op_names.get(o.name, "")
        devices.append(Device(int(m.group(1)), ops, modules))
    devices.sort(key=lambda d: d.index)
    return Reduced(devices, path, host)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), hlo_op_names(path), path)
