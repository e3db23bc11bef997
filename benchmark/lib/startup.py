"""Set-up's parts, read from the program's own compile log.

``megatron_llm_tpu/observability/compiles.py`` keeps a time-stamped row
for every function the process traced, lowered and compiled (or loaded
from the persistent cache), on ``time.monotonic()``, the benchmark's
clock (``lib/harness.py`` ``Clock``).  The readers under ``setup_s``
(``layer_metrics/setup_*.py``) take the rows with ``process_start <=
t_end <= t_open`` and add up their seconds by stage.  A program without
such a log (the parent of the PR that added it), or one whose entry point
never installed it, gives None everywhere: the metrics are left out of
the line.

The sums are of seconds on the compiling threads.  The log counts each
second of one thread once (a function traced inside another's trace is
part of the outer row), but where two threads compile at once (a serving
run's scheduler thread and the harness's reference) the sums may exceed
the wall time they are set against.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

WRAPPED = re.compile(r"^\w+\((.*)\)$")      # jit(tick) -> tick
LISTED_S = 1.0      # the table lists a cold compile this long by itself


def _log_module():
    try:
        from megatron_llm_tpu.observability import compiles
    except ImportError:
        return None
    if not (hasattr(compiles, "log") and hasattr(compiles, "installed_at")):
        return None
    return compiles


def installed_at() -> Optional[float]:
    mod = _log_module()
    return None if mod is None else mod.installed_at()


def rows(run) -> Optional[List]:
    """The log's rows of this run's set-up, oldest first; read once a run
    (the first reading prints the programs' table)."""
    cached = getattr(run, "_setup_rows", False)
    if cached is not False:
        return cached
    mod = _log_module()
    out = None
    at = None if mod is None else mod.installed_at()
    if at is not None and run.t_open is not None:
        out = [r for r in mod.log()
               if run.clock.process_start <= r.t_end <= run.t_open]
        _print_programs(run, out, at)
    run._setup_rows = out
    return out


def backend_s(run) -> Optional[float]:
    at = installed_at()
    return None if at is None else at - run.clock.process_start


def _seconds(got: List, *stages: str) -> float:
    return sum(r.seconds for r in got if r.stage in stages)


def stage_s(run, *stages: str) -> Optional[float]:
    got = rows(run)
    return None if got is None else _seconds(got, *stages)


def cold_compile_s(run) -> Optional[float]:
    got = rows(run)
    if got is None:
        return None
    return sum(r.seconds for r in got
               if r.stage == "compile" and r.outcome == "cold")


def by_program(got: List) -> Dict[str, Dict]:
    """Per function: rows, hits, cold compiles (each one of
    :data:`LISTED_S` or more by itself, in order: a tick's buckets are one
    name), seconds by stage, and when its last row ended."""
    table: Dict[str, Dict] = {}
    for r in got:
        m = WRAPPED.match(r.fun_name)
        p = table.setdefault(m.group(1) if m else r.fun_name, {
            "rows": 0, "hit": 0, "cold": 0, "each": [], "trace": 0.0,
            "lower": 0.0, "compile": 0.0, "last": r.t_end})
        p["rows"] += 1
        p[r.stage] += r.seconds
        p["last"] = max(p["last"], r.t_end)
        if r.stage == "compile":
            p["hit" if r.outcome == "hit" else "cold"] += 1
            if r.outcome != "hit" and r.seconds >= LISTED_S:
                p["each"].append(r.seconds)
    return table


def _print_programs(run, got: List, at: float, most: int = 12) -> None:
    table = by_program(got)
    total = lambda p: p["trace"] + p["lower"] + p["compile"]  # noqa: E731
    each = lambda p: "".join(f" {s:.2f}" for s in p["each"])  # noqa: E731
    names = sorted(table, key=lambda n: -total(table[n]))
    said = [f"{n} x{p['rows']} hit {p['hit']} cold {p['cold']}"
            f"{' (' + each(p)[1:] + ')' if p['each'] else ''} trace "
            f"{p['trace']:.2f} lower {p['lower']:.2f} compile "
            f"{p['compile']:.2f} last +{p['last'] - at:.1f}"
            for n, p in ((n, table[n]) for n in names[:most])]
    rest = names[most:]
    if rest:
        said.append(f"{len(rest)} more programs "
                    f"{sum(total(table[n]) for n in rest):.2f}")
    n_c = sum(r.stage == "compile" for r in got)
    hits = sum(r.outcome == "hit" for r in got)
    print(f"benchmark: set-up's programs before the window (seconds on the "
          f"compiling threads; +s after the log's start, which was "
          f"{at - run.clock.process_start:.2f} s into the process): compiles "
          f"{n_c} ({hits} hit, {n_c - hits} cold) "
          f"{_seconds(got, 'compile'):.2f} s, trace "
          f"{_seconds(got, 'trace'):.2f} s, lower "
          f"{_seconds(got, 'lower'):.2f} s; "
          + "; ".join(said), flush=True)
