"""The program's own record of its XLA compilations and of its start-up.

A recompile on a shape nobody warmed up (a new prefill bucket, a changed
batch) is the classic serving stall, a trainer that compiles twice has a
bug, and "why did this replica take four minutes to its first token" is
a question about which programs were compiled cold, which were loaded
from the persistent cache, and what was neither.  ``install()`` listens
to jax's monitoring events (a function's trace, its lowering, its
backend compile and a cache hit) and keeps:

* **the compile log** (``log()``): the newest :data:`LOG_ROWS` rows, one
  a timed region, ``Row(t_end, stage, fun_name, seconds, outcome)``.
  ``t_end`` is ``time.monotonic()`` when the listener ran, ``stage`` one
  of ``trace`` / ``lower`` / ``compile``.  ``outcome`` of a ``compile``
  row is ``hit`` where the persistent cache answered (the row's seconds
  are then the load) and ``cold`` where the backend compiled; jax fires
  the backend-compile event either way, so a hit is told by the cache-hit
  event fired on the same thread inside that timed region.  Regions nest
  on a thread (tracing ``tick`` traces jnp's own jitted helpers by the
  hundred, and may compile a constant): a trace or a lowering inside
  another region makes no row,
  and a compile inside a trace is taken off the trace's seconds, so the
  seconds of a thread's rows add up to time that thread spent, each
  second once.  Where two threads compile at once the sum over all rows
  exceeds the wall time.
* **counters on ``GET /metrics``** (no ``fun_name`` label: names are
  unbounded, they live in the log):

  - ``mlt_jit_compiles_total``               programs compiled or loaded;
  - ``mlt_jit_compile_seconds_total``        seconds spent doing so;
  - ``mlt_jit_cache_hits_total``             of those, loaded from the cache;
  - ``mlt_jit_cold_compiles_total``          of those, compiled;
  - ``mlt_jit_cold_compile_seconds_total``   seconds the compiler took;
  - ``mlt_jit_trace_seconds_total``          Python tracing (jaxprs);
  - ``mlt_jit_lower_seconds_total``          lowering to MLIR (Mosaic's
    Python lowering of a Pallas kernel too; no cache keeps it).
* **one ``jit-compile`` instant a row** in the span ring, where one is
  configured.

``startup_phase(name)`` is the other half: a ``startup`` span around a
phase of start-up (the mesh, the model's set-up, a checkpoint load, the
first step; an engine's build, the server's bind, a tick program's first
call) that also sets ``mlt_startup_phase_seconds{phase=}`` and is kept
in ``phases()``.  ``summary()`` is the one line a trainer prints when its
first step retires and an engine when its first tick is applied.

``utils/platform.enable_compilation_cache()`` installs the listeners, so
whatever an entry point compiles after placing its cache is in the log;
``install_compile_counter()`` is the same idempotent call under the name
the server and the trainer use.  Never at import.  The listeners run on
the compiling thread, touch no device and look the counters up when an
event fires: a compile is rare, and a test that clears the registry
keeps working.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

from megatron_llm_tpu.observability import trace
from megatron_llm_tpu.observability.registry import get_registry, publishing

__all__ = ["COMPILE_EVENT", "LOG_ROWS", "Row", "install",
           "install_compile_counter", "installed_at", "log", "phases",
           "startup_phase", "summary", "totals"]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_STAGES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
           COMPILE_EVENT: "compile"}

LOG_ROWS = 2048     # a serving process makes 50-200
PHASE_ROWS = 256    # a process has a handful; a test session many engines


class Row(NamedTuple):
    t_end: float                # time.monotonic() when the event fired
    stage: str                  # "trace" | "lower" | "compile"
    fun_name: str
    seconds: float
    outcome: Optional[str]      # of a compile row: "hit" | "cold"


_lock = threading.Lock()
_installed_at: Optional[float] = None           # guarded by _lock
_log: deque = deque(maxlen=LOG_ROWS)            # guarded by _lock
_phases: deque = deque(maxlen=PHASE_ROWS)       # guarded by _lock
# per compiling thread: ``open``, the timed regions it is inside, innermost
# last, each ``[event, seconds of the rows made inside it]``; and ``hit``,
# whether the cache answered inside the open compile region
_thread = threading.local()


def _on_start(event: str, _value: float, **_kw) -> None:
    """jax records a scalar under the event's name as a timed region
    opens (``dispatch.log_elapsed_time``)."""
    if event not in _STAGES:
        return
    if not hasattr(_thread, "open"):
        _thread.open = []
    _thread.open.append([event, 0.0])


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _thread.hit = True


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_kw) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    seconds, inside = max(float(duration), 0.0), 0.0
    regions = getattr(_thread, "open", None)
    if regions and regions[-1][0] == event:
        inside = regions.pop()[1]
    outer = regions[-1] if regions else None
    if outer is not None and stage != "compile":
        # a jitted function traced while another is (jnp's own helpers by
        # the hundred): no row, its seconds are the enclosing row's
        outer[1] += inside
        return
    if outer is not None:
        outer[1] += seconds     # a compile inside a trace: its own row's
    seconds = max(seconds - inside, 0.0)
    outcome = None
    if stage == "compile":
        outcome = "hit" if getattr(_thread, "hit", False) else "cold"
        _thread.hit = False
    row = Row(time.monotonic(), stage, str(fun_name), seconds, outcome)
    with _lock:
        _log.append(row)
    trace.instant("jit-compile", fun=row.fun_name, stage=stage,
                  seconds=seconds, outcome=outcome)
    if not publishing():
        return
    # literal names: the wire-metrics lint ties each to its row in the guide
    reg = get_registry()
    if stage == "trace":
        reg.counter("mlt_jit_trace_seconds_total",
                    help="seconds spent tracing functions to jaxprs"
                    ).inc(seconds)
    elif stage == "lower":
        reg.counter("mlt_jit_lower_seconds_total",
                    help="seconds spent lowering jaxprs to MLIR (Pallas "
                         "kernels' Mosaic lowering included)").inc(seconds)
    else:
        reg.counter("mlt_jit_compiles_total",
                    help="XLA programs compiled (or loaded from the "
                         "persistent cache) by this process").inc()
        reg.counter("mlt_jit_compile_seconds_total",
                    help="seconds spent in those compilations").inc(seconds)
        if outcome == "hit":
            reg.counter("mlt_jit_cache_hits_total",
                        help="of those programs, loaded from the persistent "
                             "cache").inc()
        else:
            reg.counter("mlt_jit_cold_compiles_total",
                        help="of those programs, compiled by the backend "
                             "(no cache, a miss, or under its thresholds)"
                        ).inc()
            reg.counter("mlt_jit_cold_compile_seconds_total",
                        help="seconds spent in those cold compilations"
                        ).inc(seconds)


def install() -> None:
    """Register the listeners, once per process (jax keeps listeners for
    the life of the process, so a second call would count double)."""
    global _installed_at
    with _lock:
        if _installed_at is not None:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed_at = time.monotonic()


install_compile_counter = install


def installed_at() -> Optional[float]:
    """``time.monotonic()`` of the installation; None before it."""
    with _lock:
        return _installed_at


def log() -> List[Row]:
    """A copy of the compile log, oldest row first."""
    with _lock:
        return list(_log)


class startup_phase(contextlib.ContextDecorator):
    """``with startup_phase("server-bind"):`` or, over a whole function,
    ``@startup_phase("engine-build")``: a ``startup`` span (in any
    profiler capture, and in the ring where one is configured) that at
    its exit sets ``mlt_startup_phase_seconds{phase=name}`` to the
    phase's seconds (of a name that runs again, a tick program's bucket,
    the newest) and appends ``(name, t0, t1, args)`` on
    ``time.monotonic()`` to ``phases()``.  ``t0`` and ``t1`` are the
    object's afterwards."""

    def __init__(self, name: str, **args):
        self.name, self.args = name, args
        self.t0 = self.t1 = None

    def _recreate_cm(self):
        # as a decorator: a phase of its own for every call
        return startup_phase(self.name, **self.args)

    def __enter__(self):
        self._span = trace.span("startup", phase=self.name, **self.args)
        self._span.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._span.__exit__(*exc)
        with _lock:
            _phases.append((self.name, self.t0, self.t1, self.args))
        if publishing():
            get_registry().gauge(
                "mlt_startup_phase_seconds",
                help="seconds the newest run of a start-up phase took",
                labels={"phase": self.name}).set(self.t1 - self.t0)
        return False


def phases() -> List[tuple]:
    """A copy of the start-up phases so far, ``(name, t0, t1, args)``, in
    the order they ended."""
    with _lock:
        return list(_phases)


def totals(rows: List[Row]) -> Dict[str, float]:
    """The sums ``summary()`` prints, of any cut of the log."""
    out = {"compiles": 0, "hits": 0, "cold": 0, "compile_s": 0.0,
           "trace_s": 0.0, "lower_s": 0.0}
    for r in rows:
        out[r.stage + "_s"] += r.seconds
        if r.stage == "compile":
            out["compiles"] += 1
            out["hits" if r.outcome == "hit" else "cold"] += 1
    return out


def summary() -> str:
    """The phases so far in order with their seconds, then the log's
    totals and how long after the installation they were read: what an
    operator greps for a slow start."""
    said = []
    for name, t0, t1, args in phases():
        extra = "".join(f" {k}={v}" for k, v in args.items())
        said.append(f"{name}{extra} {t1 - t0:.2f} s")
    t = totals(log())
    since = time.monotonic() - (installed_at() or time.monotonic())
    return (f"start-up: {', '.join(said) or 'no phase'}; compiles "
            f"{t['compiles']} ({t['hits']} hit, {t['cold']} cold) "
            f"{t['compile_s']:.2f} s, trace {t['trace_s']:.2f} s, lower "
            f"{t['lower_s']:.2f} s, in the log's first {since:.2f} s")
