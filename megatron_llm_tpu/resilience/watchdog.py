"""Step-deadline watchdog: turn a silent hang into a diagnosable exit.

The failure mode this targets is the worst one operationally: the process
is alive, the loop is not advancing, and nothing ever prints — a wedged
device, a deadlocked collective, a data loader blocked on a dead
filesystem.  A
supervisor cannot restart what never exits, so the watchdog's job is to
*exit*, loudly:

  1. dump every Python thread's stack to stderr (where the hang is);
  2. record a gauge (observability hook, sync-free);
  3. attempt a bounded emergency host-snapshot save (the snapshot itself
     may hang on a wedged device — it runs on a scrap thread with a
     timeout and is abandoned, never waited on, past it);
  4. ``os._exit(EXIT_WATCHDOG)`` — a DISTINCT code (43) the supervisor
     classifies as "hang" (supervisor.classify_exit).

The deadline adapts: ``multiplier × EMA(step time)`` with a floor, and a
separate generous first-step deadline because the compile step is
legitimately orders of magnitude slower than steady state.  The driver
arms before each loop iteration and disarms (feeding the EMA) after it;
long legitimate pauses (eval, sync checkpoint save) happen disarmed.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Callable, Optional

# distinct from every Python/OS convention in use: 0 clean, 1 generic
# error, 2 usage, 120-ish interpreter, 128+N signals
EXIT_WATCHDOG = 43


def dump_all_stacks(stream=None) -> None:
    """Write every live thread's Python stack to ``stream`` (stderr).
    The watchdog's first action on expiry — the hang IS one of these."""
    stream = stream or sys.stderr
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    print("=" * 60, file=stream)
    print(f"WATCHDOG: step deadline expired — dumping "
          f"{len(frames)} thread stacks", file=stream)
    for ident, frame in frames.items():
        print(f"--- thread {names.get(ident, '?')} (ident {ident}) ---",
              file=stream)
        traceback.print_stack(frame, file=stream)
    print("=" * 60, file=stream)
    stream.flush()


class StepWatchdog:
    """Arm/disarm deadline watchdog around the training loop body.

    Args:
      multiplier: deadline = multiplier × EMA(step seconds).
      min_deadline: floor in seconds (covers EMA warm-up and jitter).
      first_deadline: deadline for the first armed window (JIT compile).
      ema_alpha: EMA smoothing for fed step times.
      snapshot_fn: best-effort emergency save, run bounded on expiry.
      snapshot_timeout: seconds to wait for snapshot_fn before exiting
        anyway (it may itself hang on a wedged device).
      gauge_fn: sync-free observability hook called once on expiry.
      trace_dump_fn: dumps the span-tracer ring buffer on expiry (returns
        the written path, printed alongside the stack dump) — a hang
        report should come with a timeline.  When None, falls back to a
        text tail of the process-wide tracer (observability/trace.py) on
        the stream, if one is configured.
      flight_dump_fn: dumps the in-flight request flight records on
        expiry (returns the written path) — a serving hang should be
        attributable to a specific request state, not just thread
        stacks.  When None, falls back to a text tail of the
        process-wide recorder (observability/flight.py), if any engine
        registered one.
      exit_fn: defaults to ``os._exit`` — tests inject a recorder.
    """

    def __init__(
        self,
        multiplier: float = 10.0,
        min_deadline: float = 60.0,
        first_deadline: float = 1800.0,
        ema_alpha: float = 0.3,
        snapshot_fn: Optional[Callable[[], None]] = None,
        snapshot_timeout: float = 120.0,
        gauge_fn: Optional[Callable[[], None]] = None,
        trace_dump_fn: Optional[Callable[[], Optional[str]]] = None,
        flight_dump_fn: Optional[Callable[[], Optional[str]]] = None,
        exit_fn: Callable[[int], None] = os._exit,
        exit_code: int = EXIT_WATCHDOG,
        stream=None,
    ):
        self.multiplier = float(multiplier)
        self.min_deadline = float(min_deadline)
        self.first_deadline = float(first_deadline)
        self.ema_alpha = float(ema_alpha)
        self._snapshot_fn = snapshot_fn
        self._snapshot_timeout = float(snapshot_timeout)
        self._gauge_fn = gauge_fn
        self._trace_dump_fn = trace_dump_fn
        self._flight_dump_fn = flight_dump_fn
        self._exit_fn = exit_fn
        self._exit_code = exit_code
        self._stream = stream
        self._ema: Optional[float] = None  # driver-thread only (no lock)
        self._deadline: Optional[float] = None  # guarded by _cond
        self._cond = threading.Condition()
        self._shutdown = False  # guarded by _cond
        self.expired = False
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="step-watchdog"
        )

    # ---- driver side ----

    def start(self) -> "StepWatchdog":
        self._thread.start()
        return self

    def current_deadline(self, first: bool = False) -> float:
        if first or self._ema is None:
            return max(self.first_deadline, self.min_deadline)
        return max(self.min_deadline, self.multiplier * self._ema)

    def arm(self, first: bool = False) -> None:
        with self._cond:
            self._deadline = time.monotonic() + self.current_deadline(first)
            self._cond.notify()

    def disarm(self, step_time: Optional[float] = None) -> None:
        """Cancel the deadline; ``step_time`` (when given) feeds the EMA."""
        with self._cond:
            self._deadline = None
            self._cond.notify()
        if step_time is not None and step_time > 0:
            if self._ema is None:
                self._ema = float(step_time)
            else:
                a = self.ema_alpha
                self._ema = a * float(step_time) + (1 - a) * self._ema

    def stop(self) -> None:
        """Normal shutdown (driver exiting): the watchdog must never
        outlive the loop it guards."""
        with self._cond:
            self._shutdown = True
            self._deadline = None
            self._cond.notify()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    # ---- watchdog thread ----

    def _run(self) -> None:
        while True:
            with self._cond:
                if self._shutdown:
                    return
                if self._deadline is None:
                    self._cond.wait(timeout=1.0)
                    continue
                remaining = self._deadline - time.monotonic()
                if remaining > 0:
                    self._cond.wait(timeout=min(remaining, 1.0))
                    continue
                # armed and past deadline: expire (outside the lock, so a
                # slow stack dump cannot deadlock arm/disarm callers)
            self._expire()
            return

    def _expire(self) -> None:
        self.expired = True
        try:
            dump_all_stacks(self._stream)
        except Exception:
            pass
        self._dump_trace()
        self._dump_flight()
        if self._gauge_fn is not None:
            try:
                self._gauge_fn()
            except Exception:
                pass
        if self._snapshot_fn is not None:
            self._emergency_snapshot()
        self._exit_fn(self._exit_code)

    def _dump_trace(self) -> None:
        """Land the span-timeline next to the stack dump (the timeline
        says WHAT the loop was doing when it stopped; the stacks say
        where it is stuck).  Best-effort on every path."""
        stream = self._stream or sys.stderr
        try:
            if self._trace_dump_fn is not None:
                path = self._trace_dump_fn()
                if path:
                    print(f"WATCHDOG: span trace dumped to {path}",
                          file=stream, flush=True)
                return
            from megatron_llm_tpu.observability import trace as obs_trace

            tracer = obs_trace.get_tracer()
            if tracer is not None and tracer.enabled:
                tracer.write_text(stream)
        except Exception:
            pass

    def _dump_flight(self) -> None:
        """Land the in-flight request flight records next to the stack
        and trace dumps (ISSUE 12): the stacks say WHERE the process is
        stuck, the timeline WHAT it was doing, the flight records WHICH
        request it was doing it for.  Best-effort on every path."""
        stream = self._stream or sys.stderr
        try:
            if self._flight_dump_fn is not None:
                path = self._flight_dump_fn()
                if path:
                    print(f"WATCHDOG: flight records dumped to {path}",
                          file=stream, flush=True)
                return
            from megatron_llm_tpu.observability import flight as obs_flight

            rec = obs_flight.get_recorder()
            if rec is not None and rec.enabled:
                rec.write_text(stream)
        except Exception:
            pass

    def _emergency_snapshot(self) -> None:
        """Run the snapshot bounded: it is best-effort by definition — a
        wedged device hangs ``device_get`` too, and the whole point of the
        watchdog is to exit regardless."""
        stream = self._stream or sys.stderr
        done = threading.Event()
        err: list = []

        def _go():
            try:
                self._snapshot_fn()
            except BaseException as e:  # noqa: BLE001 — report, then exit
                err.append(e)
            finally:
                done.set()

        t = threading.Thread(target=_go, daemon=True,
                             name="watchdog-emergency-save")
        t.start()
        if not done.wait(self._snapshot_timeout):
            print(f"WATCHDOG: emergency snapshot did not finish within "
                  f"{self._snapshot_timeout}s — exiting without it",
                  file=stream, flush=True)
        elif err:
            print(f"WATCHDOG: emergency snapshot failed: {err[0]!r}",
                  file=stream, flush=True)
        else:
            print("WATCHDOG: emergency snapshot saved", file=stream,
                  flush=True)
