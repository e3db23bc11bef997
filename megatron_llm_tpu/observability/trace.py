"""Sync-free host-side spans: one ``span()``, two sinks.

Every ``span(name, **args)`` enters a ``jax.profiler.TraceAnnotation``,
so it lands in any ``jax.profiler`` capture (``--profile``, the on-demand
``/profile`` windows, a benchmark's traced run) on plane ``/host:CPU``,
one line per thread, on the same clock as the device planes — a device
idle gap can be laid over the phase of the program that was open.  While
no capture runs the annotation costs an atomic read.  When a ring is
configured (``configure()``: ``--trace_dir``, the watchdog's hang
report) the same span is also recorded there, as before.

The async training loop (training.py) and the decode engine
(generation/engine.py) deliberately keep the host off the device's
critical path; a tracer that synchronized — or even allocated without
bound — would undo exactly the overlap it is supposed to make visible
(T3, PAPERS.md: overlap is only tunable when it can be SEEN).  So this
module obeys two hard rules, enforced by the ``obs-no-sync`` graftcheck
rule (docs/guide/static-analysis.md): nothing in ``observability/`` may
touch the device, and every record is O(1) into a fixed-capacity ring
(old events drop, the hot path never blocks on I/O).

Usage::

    from megatron_llm_tpu.observability import trace

    trace.configure(capacity=65536)        # process-wide tracer, once
    with trace.span("data-wait", iteration=i):
        batch = next(loader)               # any thread
    trace.instant("step", iteration=i)
    trace.get_tracer().dump("trace_000010.json")   # Chrome trace JSON

When no tracer is configured (the default), ``span()`` returns the bare
profiler annotation (under a microsecond with no capture running) and
``instant()``, which is ring-only, is a no-op.  ``jax`` is imported at
the first span, not with this module.

The dump format is the Chrome/Perfetto ``traceEvents`` JSON (load it at
https://ui.perfetto.dev or chrome://tracing): complete ``"X"`` events
with microsecond ``ts``/``dur``, ``"i"`` instants, and thread-name
metadata rows so the driver / prefetch / checkpoint-writer / engine
threads come out labelled.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = [
    "SpanTracer",
    "configure",
    "disable",
    "get_tracer",
    "instant",
    "span",
]


class _NullContext:
    """Reusable no-op context: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullContext()


class _Span:
    """Context manager recording one complete ("X") event on exit, inside
    the profiler annotation ``ann`` when one is given."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]], ann=_NULL):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tracer._record("X", self._name, self._t0, t1 - self._t0,
                             self._args)
        self._ann.__exit__(*exc)
        return False


class SpanTracer:
    """Bounded in-memory event ring; thread-safe; never touches a device.

    Events are ``(ph, name, ts_s, dur_s, thread_ident, args)`` tuples with
    host ``time.perf_counter`` timestamps relative to the tracer's epoch.
    The ring holds the newest ``capacity`` events; older ones drop (the
    ``dropped`` counter keeps the tally honest in dumps).
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.capacity = max(int(capacity), 16)
        self.enabled = bool(enabled)
        self._epoch = time.perf_counter()
        self._buf: deque = deque(maxlen=self.capacity)  # guarded by _lock
        self._lock = threading.Lock()
        self._total = 0  # guarded by _lock
        # evictions, NOT reset by drain (honest dumps) — guarded by _lock
        self._dropped = 0

    # ---- recording (hot path) ----

    def span(self, name: str, **args) -> Any:
        """Context manager timing a named phase on the calling thread."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (step boundaries, triggers)."""
        if not self.enabled:
            return
        self._record("i", name, time.perf_counter(), 0.0, args or None)

    def _record(self, ph: str, name: str, t0: float, dur: float,
                args: Optional[Dict[str, Any]]) -> None:
        ident = threading.get_ident()
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1  # append below evicts the oldest
            self._buf.append((ph, name, t0 - self._epoch, dur, ident, args))
            self._total += 1

    # ---- inspection / export ----

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    @property
    def dropped(self) -> int:
        """Events pushed out of the ring by newer ones (drains — which
        consume events deliberately — do not count)."""
        with self._lock:
            return self._dropped

    def snapshot(self, drain: bool = False) -> List[tuple]:
        """A consistent copy of the ring (oldest first); optionally clears
        it, starting a fresh window."""
        with self._lock:
            events = list(self._buf)
            if drain:
                self._buf.clear()
            return events

    def to_chrome_trace(self, events: Optional[List[tuple]] = None) -> Dict:
        """Build the Chrome/Perfetto ``traceEvents`` document.

        Thread names are resolved from the live thread table at dump time
        (recording stores only the ident — name lookups are too slow for
        the hot path); threads that already exited keep their ident."""
        if events is None:
            events = self.snapshot()
        pid = os.getpid()
        names = {t.ident: t.name for t in threading.enumerate()}
        rows: List[Dict[str, Any]] = []
        seen_tids = set()
        for ph, name, ts, dur, tid, args in events:
            row: Dict[str, Any] = {
                "name": name, "ph": ph, "pid": pid, "tid": tid,
                "ts": round(ts * 1e6, 3),
            }
            if ph == "X":
                row["dur"] = round(dur * 1e6, 3)
            if args:
                row["args"] = args
            rows.append(row)
            seen_tids.add(tid)
        for tid in sorted(seen_tids):
            rows.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": names.get(tid, f"thread-{tid}")},
            })
        return {
            "traceEvents": rows,
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_events": self.dropped,
                "capacity": self.capacity,
            },
        }

    def dump(self, path: str, drain: bool = True) -> str:
        """Write a Chrome-trace JSON file atomically; returns ``path``.

        ``drain=True`` (the default) clears the ring, so successive dumps
        are disjoint N-step windows; ``drain=False`` leaves the ring
        intact (the watchdog's crash dump must not consume evidence)."""
        doc = self.to_chrome_trace(self.snapshot(drain=drain))
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def write_text(self, stream, limit: int = 200) -> None:
        """Human-readable tail of the ring (newest last) — the watchdog's
        fallback when no trace dir is configured: a hang report should
        carry a timeline even without ``--trace_dir``."""
        events = self.snapshot()
        if not events:
            return
        print(f"TRACE: last {min(limit, len(events))} of {len(events)} "
              f"buffered events (dropped {self.dropped}):", file=stream)
        for ph, name, ts, dur, tid, args in events[-limit:]:
            extra = f" {args}" if args else ""
            if ph == "X":
                print(f"  {ts:12.6f}s +{dur * 1e3:9.3f}ms  {name} "
                      f"[tid {tid}]{extra}", file=stream)
            else:
                print(f"  {ts:12.6f}s     (mark)    {name} "
                      f"[tid {tid}]{extra}", file=stream)
        stream.flush()


# ---------------------------------------------------------------------------
# Process-wide tracer (the instrumented modules all share one)
# ---------------------------------------------------------------------------

_TRACER: Optional[SpanTracer] = None


def configure(capacity: int = 65536) -> SpanTracer:
    """Install (or replace) the process-wide tracer and return it."""
    global _TRACER
    _TRACER = SpanTracer(capacity=capacity, enabled=True)
    return _TRACER


def disable() -> None:
    """Drop the process-wide tracer: ``span()`` reverts to the null path."""
    global _TRACER
    _TRACER = None


def get_tracer() -> Optional[SpanTracer]:
    return _TRACER


_ANNOTATION = None  # jax.profiler.TraceAnnotation, resolved at first use


def span(name: str, **args) -> Any:
    """What the instrumented hot paths call: a profiler annotation always
    (visible in any ``jax.profiler`` capture), and a ring record as well
    when a process-wide tracer is configured."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    ann = _ANNOTATION(name, **args)
    t = _TRACER
    if t is None or not t.enabled:
        return ann
    return _Span(t, name, args or None, ann)


def instant(name: str, **args) -> None:
    t = _TRACER
    if t is None or not t.enabled:
        return
    t.instant(name, **args)
