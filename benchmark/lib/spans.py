"""The program's own spans, read out of the capture a traced run took.

``observability/trace.py`` ``span()`` enters a ``jax.profiler.TraceAnnotation``,
so every span of the program is an event on plane ``/host:CPU`` of the same
``*.xplane.pb`` the device planes are in, one line per thread, on the device
planes' clock.  This module reads them (name, thread, start, end, arguments),
rebuilds the nesting on each thread, and lays them over device 0's idle
stretches and tick executions.  A program without such spans (the parent of
the PR that added them) gives an empty list, and every function here then
returns None: the metric is left out of the line.

Definitions:

* thread      the index of the event's line in the plane (lines carry no id)
* scheduler   the thread that holds the ``engine-step`` spans
* idle        the stretches of device 0's window with no op running, as
              ``lib/trace.py`` ``Device.busy`` has them
* phase       one of PHASES: the scheduler thread's leaf spans of a step
"""

from __future__ import annotations

import re
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.lib import readers
from benchmark.lib.trace import HOST_PLANE, _minus, _union

# every span name the program emits on a cell's path (observability guide)
SPAN_NAMES = frozenset((
    "engine-step", "engine-admit", "engine-plan", "engine-ragged-tick",
    "engine-launch", "engine-fetch", "engine-apply", "engine-wait",
    "engine-enqueue", "engine-prefill-chunk",
    "serve-api", "serve-api-stream", "serve-write",
    "data-wait", "dispatch", "metric-drain", "place-batch",
    "ckpt-flush", "ckpt-write", "eval"))
# the scheduler thread's phases: between them they should hold every idle
# nanosecond; what they do not hold is "unattributed"
PHASES = ("engine-admit", "engine-plan", "engine-launch", "engine-fetch",
          "engine-apply", "engine-wait")
Interval = Tuple[float, float]


class Span:
    __slots__ = ("name", "thread", "start", "end", "args", "parent")

    def __init__(self, name: str, thread: int, start: float, end: float,
                 args: Dict):
        self.name, self.thread = name, thread
        self.start, self.end, self.args = start, end, args
        self.parent: Optional["Span"] = None

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, thread {self.thread}, "
                f"{self.start:.0f}-{self.end:.0f}, {self.args})")


def from_profile(profile, names: Iterable[str] = SPAN_NAMES) -> List[Span]:
    """Spans of a ``jax.profiler.ProfileData``, parents set by nesting on
    each thread, ordered by start."""
    names = frozenset(names)
    out: List[Span] = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for thread, line in enumerate(plane.lines):
            mine = [Span(e.name, thread, e.start_ns,
                         e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events if e.name in names]
            stack: List[Span] = []
            for sp in sorted(mine, key=lambda s: (s.start, -s.end)):
                while stack and stack[-1].end <= sp.start:
                    stack.pop()
                if stack and sp.end <= stack[-1].end:
                    sp.parent = stack[-1]
                stack.append(sp)
            out += mine
    return sorted(out, key=lambda s: s.start)


def of(run) -> List[Span]:
    """The spans of a run's capture, read once (the file also holds every
    Python call of every thread, so reading it is not free)."""
    if run.trace is None or not run.trace.path:
        return []
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        from jax.profiler import ProfileData

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            cached = from_profile(ProfileData.from_file(run.trace.path))
        run._program_spans = cached
        print(f"benchmark: {len(cached)} program spans in the capture "
              f"({len({s.thread for s in cached})} threads)", flush=True)
    return cached


# ---- intervals -------------------------------------------------------------

def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    return sum(e - s for s, e in a) - _minus(a, b)


def idle_intervals(reduced) -> List[Interval]:
    """Device 0's idle stretches, in ns, inside its own window."""
    if reduced is None or not reduced.devices or not reduced.devices[0].busy:
        return []
    b = reduced.devices[0].busy
    return [(b[i][1], b[i + 1][0]) for i in range(len(b) - 1)
            if b[i + 1][0] > b[i][1]]


def scheduler_thread(spans: List[Span]) -> Optional[int]:
    steps = [s.thread for s in spans if s.name == "engine-step"]
    return max(set(steps), key=steps.count) if steps else None


def idle_by_span(reduced, spans: List[Span],
                 names: Optional[Iterable[str]] = None
                 ) -> Optional[Dict[Optional[str], float]]:
    """Seconds of device-0 idle time by the *innermost* scheduler-thread
    span open at the time (of the spans called one of ``names``, if given:
    time in a span's child that is not in ``names`` counts for the span);
    key None holds the idle seconds under no such span.  The values sum to
    the idle total."""
    thread = scheduler_thread(spans)
    idle = idle_intervals(reduced)
    if thread is None or not idle:
        return None
    mine = [s for s in spans if s.thread == thread
            and (names is None or s.name in names)]
    # a span's own stretches: its interval minus its children's (children
    # among `mine`: a parent outside `names` is looked through)
    kids: Dict[int, List[Interval]] = {}
    for s in mine:
        p = s.parent
        while p is not None and names is not None and p.name not in names:
            p = p.parent
        if p is not None:
            kids.setdefault(id(p), []).append((s.start, s.end))
    out: Dict[Optional[str], float] = {None: 0.0}
    covered = 0.0
    for s in mine:
        inner = _union(kids.get(id(s), []))
        own, cur = [], s.start
        for a, b in inner:
            if a > cur:
                own.append((cur, a))
            cur = max(cur, b)
        if cur < s.end:
            own.append((cur, s.end))
        sec = _overlap(own, idle) / 1e9
        out[s.name] = out.get(s.name, 0.0) + sec
        covered += sec
    out[None] = max(sum(e - s for s, e in idle) / 1e9 - covered, 0.0)
    return out


def idle_under(reduced, spans: List[Span], name: str) -> Optional[float]:
    """Seconds of device-0 idle time during which at least one span called
    ``name`` was open on any thread."""
    idle = idle_intervals(reduced)
    if not idle or not any(s.name == name for s in spans):
        return None
    return _overlap(_union((s.start, s.end) for s in spans
                           if s.name == name), idle) / 1e9


def idle_seconds(reduced) -> float:
    return sum(e - s for s, e in idle_intervals(reduced)) / 1e9


def launch_kinds(reduced, spans: List[Span]) -> Optional[List[Dict]]:
    """Every whole execution of the tick program on device 0 with the
    ``engine-launch`` span that dispatched it: the last one that started
    before the execution did.  Whole: its launch is in the capture (the
    capture did not open after it) and so is the end of the fetch that
    waited for it.  Each entry: ``start``, ``end`` (ns), ``prefill_rows``,
    ``launch``, ``fetch`` (the launch's sibling), ``on_clock`` (launch
    started before the execution, fetch ended after it).  A launch claimed
    by two executions, or an execution without one, marks the entry
    ``ambiguous``."""
    thread = scheduler_thread(spans)
    if thread is None or reduced is None or not reduced.devices:
        return None
    rx = re.compile(readers.TICK_PROGRAM)
    runs = sorted((s, e) for name, s, e in reduced.devices[0].modules
                  if rx.search(name))
    launches = [s for s in spans
                if s.name == "engine-launch" and s.thread == thread]
    fetches = [s for s in spans
               if s.name == "engine-fetch" and s.thread == thread]
    if not runs or not launches:
        return None
    out: List[Dict] = []
    claimed: Dict[int, int] = {}
    for start, end in runs:
        before = [s for s in launches if s.start <= start]
        if not before:
            continue                      # launched before the capture opened
        launch = before[-1]
        fetch = next((f for f in fetches if f.start >= launch.end), None)
        if fetch is None:
            continue                      # the capture closed before its fetch
        claimed[id(launch)] = claimed.get(id(launch), 0) + 1
        out.append({"start": start, "end": end, "launch": launch,
                    "fetch": fetch,
                    "prefill_rows": int(launch.args.get("prefill_rows", 0)),
                    "on_clock": launch.start <= start and fetch.end >= end})
    for entry in out:
        entry["ambiguous"] = claimed[id(entry["launch"])] != 1
    return out


def report_launches(run) -> None:
    """One line a traced run: how many whole tick executions were matched
    to their launch, and whether the spans sit on the device's clock."""
    kinds = launch_kinds(run.trace, of(run))
    if not kinds:
        return
    off = sum(1 for k in kinds if not k["on_clock"])
    odd = sum(1 for k in kinds if k["ambiguous"])
    print(f"benchmark: {len(kinds)} whole tick executions matched to a "
          f"launch, {sum(k['prefill_rows'] > 0 for k in kinds)} with prefill "
          f"rows; {odd} ambiguous, {off} with the launch after or the fetch "
          f"before the execution (clock check)", flush=True)


def phase_shares(run) -> Optional[Dict[Optional[str], float]]:
    """% of device-0 idle time under each of the scheduler's PHASES (key
    None: under none of them), worked out and printed once a run."""
    cached = getattr(run, "_idle_phase_shares", None)
    if cached is None:
        by = idle_by_span(run.trace, of(run), PHASES)
        total = idle_seconds(run.trace)
        if by is None or total <= 0:
            return None
        cached = {k: 100.0 * v / total for k, v in by.items()}
        run._idle_phase_shares = cached
        print("benchmark: device idle by scheduler phase: " + ", ".join(
            f"{k or 'none'} {v:.2f}%" for k, v in sorted(
                cached.items(), key=lambda kv: -kv[1]))
            + f" of {total * 1e3:.1f} ms idle", flush=True)
        report_launches(run)
    return cached


def idle_share(run, names: Iterable[Optional[str]]) -> Optional[float]:
    shares = phase_shares(run)
    if shares is None:
        return None
    return sum(shares.get(n, 0.0) for n in names)
