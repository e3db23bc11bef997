"""Whole-window program counters over the window's ticks, for the readers
that price the host's work a tick (``layer_metrics/pool_*.batch.py``,
``plan_upload_ms.batch.py``, ``host_offcpu_ms.batch.py``).  A program
without one of the names (the parent of the PR that added the counter)
gives None and the metric is left out of the line."""

from __future__ import annotations

from typing import Iterable, Optional

from benchmark.lib import readers

TICKS = "mlt_engine_ticks_total"


def total(run, names: Iterable[str]) -> Optional[float]:
    """Sum of the named counters over the window; None if any is absent."""
    vals = [readers.counter(run, n) for n in names]
    return None if any(v is None for v in vals) else float(sum(vals))


def ms(run, plus: Iterable[str], minus: Iterable[str] = ()) -> Optional[float]:
    """(sum of ``plus`` - sum of ``minus``) seconds, in ms a tick."""
    ticks = readers.counter(run, TICKS)
    a, b = total(run, plus), total(run, minus)
    if not ticks or a is None or b is None:
        return None
    return 1e3 * (a - b) / ticks
