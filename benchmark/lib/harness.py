"""What every kind of run shares: the clock, the compilation counter, the
profiler window, the observations a run hands to the metric readers, and
the contract's last line."""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from benchmark.lib import peaks as peaks_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Clock:
    """``time.monotonic()`` throughout: one system-wide clock on Linux, so
    the client child's stamps compare with the parent's."""

    def __init__(self, process_start: float):
        self.process_start = process_start

    @staticmethod
    def now() -> float:
        return time.monotonic()


class CompileCounter:
    """Every backend compile (or persistent-cache load) of the process,
    with the time it ended.  ``inside(a, b)`` is what must be zero for a
    measured window."""

    def __init__(self):
        import jax.monitoring

        self.events: List[tuple] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.monotonic(), float(duration)))

    def inside(self, t_open: float, t_close: float) -> int:
        with self._lock:
            return sum(1 for t, _ in self.events if t_open <= t <= t_close)

    def total(self) -> int:
        with self._lock:
            return len(self.events)


class Run:
    """Observations of one run; the metric readers' only input.  A kind's
    runner fills what it has; a reader that finds nothing returns None."""

    def __init__(self, cell, args, clock: Clock):
        self.cell, self.args, self.clock = cell, args, clock
        self.seconds = float(args.seconds)
        self.kind = cell.traffic["kind"]
        self.chips = cell.chips
        self.device: Dict[str, Any] = {}
        self.peaks: Optional[Dict[str, float]] = None
        self.t_open = self.t_close = None    # the measured window
        self.setup_s: Optional[float] = None
        self.correct = False
        self.checks: Dict[str, Any] = {}     # what `correct` rests on
        self.attempted = self.failed = 0
        self.compiles_in_window: Optional[int] = None
        # train
        self.draw_t: List[float] = []        # when each batch was drawn
        self.tokens_per_step = 0
        self.first_window_draw = self.last_window_draw = None
        self.gauges: Dict[str, List[tuple]] = {}   # name -> [(t, value)]
        self.disturbed_from: Optional[float] = None  # profiler start
        self.program: Dict[str, Any] = {}    # the program's own figures
        # serve
        self.samples: List[Dict] = []        # measured requests
        self.all_samples: List[Dict] = []
        self.counters: Dict[str, float] = {}  # /metrics close - open
        self.engine: Dict[str, Any] = {}     # slots, page size, chunk
        # trace
        self.trace = None                    # lib.trace.Reduced
        self.trace_host: Optional[tuple] = None  # (start, stop) monotonic

    @property
    def window_s(self) -> Optional[float]:
        if self.t_open is None or self.t_close is None:
            return None
        return self.t_close - self.t_open

    def stamp_device(self) -> None:
        import jax

        devs = jax.devices()[: self.chips] if self.chips else jax.devices()
        d0 = devs[0]
        self.device = {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devs)}
        if d0.platform == "tpu":
            self.peaks = peaks_mod.peaks_for(d0.device_kind)

    def memory_peak(self) -> int:
        """The allocator's peak on the fullest chip.  On this runtime it
        counts live buffers, not a program's temporaries (PERF.md section
        6): a floor for the true peak, not the peak."""
        import jax

        peak = 0
        for d in jax.devices()[: self.chips]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


def out_dir(cell, leaf: str) -> str:
    """A scratch directory inside the checkout (``benchmark/.out/`` is in
    .gitignore); traces and client samples never leave it."""
    path = os.path.join(cell.bench_dir, ".out", cell.name, leaf)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


class Profiler:
    """A few seconds (or steps) of ``jax.profiler`` in the middle of the
    window, then the reduction."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = out_dir(run.cell, "trace")
        self.started = self.stopped = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)   # takes about a second
        self.started = time.monotonic()

    def stop(self) -> None:
        import jax

        self.stopped = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self) -> None:
        from benchmark.lib import trace as trace_mod

        path = trace_mod.find_xplane(self.dir)
        if path is None:
            print("benchmark: the profiler wrote no trace", flush=True)
            return
        self.run.trace = trace_mod.reduce_file(path)
        self.run.trace_host = (self.started, self.stopped)


def _read_metrics(cell, run: Run, group: str, subdir: str) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    for m in getattr(cell, group):
        try:
            path = os.path.join(cell.bench_dir, subdir, m["name"] + ".py")
            if not os.path.isfile(path):
                print(f"benchmark: no reader {path}", flush=True)
                continue
            value = cell.reader_at(path).reduce(run)
        except Exception:   # a reader's fault must not lose the run's line
            print(f"benchmark: reader {m['name']} failed:\n"
                  + traceback.format_exc(), file=sys.stderr, flush=True)
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _plain(v):
    """numpy scalars and arrays as plain Python, for json."""
    if isinstance(v, dict):
        return {k: _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    return v.item() if hasattr(v, "item") else v


def compared(cell, checks: Dict) -> Dict[str, Dict]:
    """Each number that ``correct`` compares beside its limit, by short
    plain names: what the last lines of a run's standard error and the last
    key of its result line show."""
    tol = cell.config.get("tolerance", {})
    pairs = (("compiles_in_window", "compiles_in_window", 0),
             ("reference_mean", "reference_mean_abs_diff", tol.get("mean_abs_nats")),
             ("reference_median", "reference_median_abs_diff",
              tol.get("median_abs_nats")),
             ("reference_max", "reference_max_abs_diff", tol.get("max_abs_nats")),
             ("engine_failures", "engine_failures", 0),
             ("prefix_hit_share", "prefix_hit_share", checks.get("min_hit_share")))
    return {name: {"value": checks[key], "limit": limit}
            for name, key, limit in pairs
            if checks.get(key) is not None and limit is not None}


def result_line(cell, args, run: Run) -> Dict:
    """The contract's one JSON object.  ``--trace 0``: the cell's
    end-to-end metrics; ``--trace 1``: its per-layer metrics."""
    if args.trace:
        metrics = _read_metrics(cell, run, "per_layer", "layer_metrics")
    else:
        metrics = _read_metrics(cell, run, "end_to_end", "end_to_end")
    device = dict(run.device)
    device["memory_peak_bytes"] = run.memory_peak()
    line: Dict[str, Any] = {
        "correct": bool(run.correct) and not args.rehearsal and args.rate is None,
        "attempted": int(run.attempted), "failed": int(run.failed),
        "metrics": metrics, "device": device,
        "workload": cell.name, "seed": args.seed, "checks": _plain(run.checks),
    }
    if args.rehearsal:
        line["rehearsal"] = True
    if args.rate is not None:
        line["sweep_rate_per_s"] = args.rate
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.labelled_gaps(10)}
    line["compared"] = compared(cell, line["checks"])      # last, by design
    return line
