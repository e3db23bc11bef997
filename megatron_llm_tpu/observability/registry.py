"""Process-wide metrics registry with Prometheus text exposition.

One registry per process (``get_registry()``), fed from every subsystem:
``utils/timers.py`` Timers+Gauges mirror into it, the resilience goodput
tracker publishes its report, the training driver publishes throughput /
MFU, and the decode engine publishes tick/slot telemetry.  The exporter
(observability/exporter.py) renders it on ``GET /metrics`` in the
Prometheus text format (version 0.0.4), so a live job is scrapeable with
a stock Prometheus/Grafana stack.

Hot-path rules (the same contract as trace.py, lint-enforced): pure host
arithmetic, O(1) per update, a plain ``threading.Lock`` per instrument —
never any device work.  Publishing can be switched off process-wide
(``set_publishing(False)``) so the overhead benchmark
(bench_observability.py) can measure instrumented-vs-not honestly; the
instruments themselves keep working either way (``publishing()`` is the
gate the *publishers* check, not the registry).
"""

from __future__ import annotations

import bisect
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Counter",
    "GaugeMetric",
    "Histogram",
    "LATENCY_BUCKETS",
    "MetricsRegistry",
    "get_registry",
    "publishing",
    "sanitize_metric_name",
    "set_publishing",
]

_NAME_BAD = re.compile(r"[^a-zA-Z0-9_:]")
_NAME_LEAD = re.compile(r"^[^a-zA-Z_:]")

# Prometheus histogram default buckets (seconds-flavored)
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                   60.0, float("inf"))

# One geometric ladder for every ``*_seconds`` histogram whose median or
# tail somebody wants to read: ratio 10^(1/8) from 0.1 ms to 100 s, 49
# bounds, so a quantile read off the buckets (``Histogram.quantile``) is
# within +-15% of the sample's.  ``_sum`` and ``_count`` stay exact.
LATENCY_BUCKETS = tuple(10.0 ** (k / 8.0 - 4.0) for k in range(49))


def sanitize_metric_name(name: str) -> str:
    """Coerce an arbitrary name ('data-wait-ms') into the Prometheus
    grammar ``[a-zA-Z_:][a-zA-Z0-9_:]*`` ('data_wait_ms')."""
    name = _NAME_BAD.sub("_", name)
    if _NAME_LEAD.match(name):
        name = "_" + name
    return name


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label_value(s: str) -> str:
    return (s.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    return f"{v:.10g}"


class _Instrument:
    __slots__ = ("_lock",)

    def __init__(self):
        self._lock = threading.Lock()


class Counter(_Instrument):
    """Monotonically increasing total."""

    __slots__ = ("_value",)

    def __init__(self):
        super().__init__()
        self._value = 0.0  # guarded by _lock

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError("counters only go up; use a gauge")
        with self._lock:
            self._value += v

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class GaugeMetric(_Instrument):
    """Last-written instantaneous value (may go up or down)."""

    __slots__ = ("_value",)

    def __init__(self):
        super().__init__()
        self._value = 0.0  # guarded by _lock

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def add(self, v: float) -> None:
        with self._lock:
            self._value += v

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram(_Instrument):
    """Cumulative-bucket histogram (Prometheus semantics)."""

    __slots__ = ("buckets", "_counts", "_sum", "_count")

    def __init__(self, buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__()
        bs = sorted(float(b) for b in buckets)
        if not bs or bs[-1] != float("inf"):
            bs.append(float("inf"))
        self.buckets = tuple(bs)
        self._counts = [0] * len(self.buckets)  # guarded by _lock
        self._sum = 0.0    # guarded by _lock
        self._count = 0    # guarded by _lock

    def observe(self, v: float) -> None:
        # first bound >= v; the last is +Inf, so there always is one
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._sum += v
            self._count += 1
            self._counts[i] += 1

    def snapshot(self) -> Tuple[List[int], float, int]:
        """(cumulative per-bucket counts, sum, count)."""
        with self._lock:
            cum, acc = [], 0
            for c in self._counts:
                acc += c
                cum.append(acc)
            return cum, self._sum, self._count

    def quantile(self, q: float) -> Optional[float]:
        """The ``q`` quantile as the buckets can tell it: the rank's place
        inside its bucket, interpolated geometrically between the bounds
        (the ladders here are geometric).  None while empty."""
        cum, _, count = self.snapshot()
        if not count:
            return None
        rank = q * count
        i = next(k for k, c in enumerate(cum) if c >= rank and c > 0)
        hi = self.buckets[i]
        if hi == float("inf"):
            return self.buckets[i - 1] if i else None
        lo = self.buckets[i - 1] if i else hi / 10.0 ** 0.125
        below = cum[i - 1] if i else 0
        frac = (rank - below) / max(cum[i] - below, 1)
        if lo <= 0:
            return lo + (hi - lo) * frac
        return lo * (hi / lo) ** min(max(frac, 0.0), 1.0)


class _Family:
    """All instruments sharing one metric name (distinct label sets)."""

    __slots__ = ("name", "kind", "help", "children")

    def __init__(self, name: str, kind: str, help_: str):
        self.name = name
        self.kind = kind
        self.help = help_
        # label tuple (sorted (k, v) pairs) -> instrument
        self.children: Dict[Tuple, _Instrument] = {}


class MetricsRegistry:
    """Thread-safe name -> instrument table with text exposition.

    ``counter`` / ``gauge`` / ``histogram`` are get-or-create: the first
    call fixes the type (and help text); a later call under a different
    type raises — one name, one meaning, as Prometheus requires.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}  # guarded by _lock

    # ---- get-or-create ----

    def _get(self, name: str, kind: str, help_: str,
             labels: Optional[Dict[str, str]], factory) -> _Instrument:
        name = sanitize_metric_name(name)
        key = tuple(sorted((k, str(v)) for k, v in (labels or {}).items()))
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name, kind, help_)
            elif fam.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}, "
                    f"requested {kind}")
            inst = fam.children.get(key)
            if inst is None:
                inst = fam.children[key] = factory()
            return inst

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get(name, "counter", help, labels, Counter)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> GaugeMetric:
        return self._get(name, "gauge", help, labels, GaugeMetric)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get(name, "histogram", help, labels,
                         lambda: Histogram(buckets))

    # ---- introspection / tests ----

    def clear(self) -> None:
        with self._lock:
            self._families.clear()

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._families)

    # ---- exposition ----

    @staticmethod
    def _labels_text(key: Tuple, extra: str = "") -> str:
        parts = [f'{k}="{_escape_label_value(v)}"' for k, v in key]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def render(self) -> str:
        """The Prometheus text format (0.0.4): HELP/TYPE headers + one
        sample line per (labelset, series)."""
        with self._lock:
            families = [(f.name, f.kind, f.help, dict(f.children))
                        for f in self._families.values()]
        out: List[str] = []
        for name, kind, help_, children in sorted(families):
            if help_:
                out.append(f"# HELP {name} {_escape_help(help_)}")
            out.append(f"# TYPE {name} {kind}")
            for key in sorted(children):
                inst = children[key]
                if kind == "histogram":
                    cum, total, count = inst.snapshot()
                    for b, c in zip(inst.buckets, cum):
                        le = self._labels_text(key, f'le="{_fmt(b)}"')
                        out.append(f"{name}_bucket{le} {c}")
                    lt = self._labels_text(key)
                    out.append(f"{name}_sum{lt} {_fmt(total)}")
                    out.append(f"{name}_count{lt} {count}")
                else:
                    lt = self._labels_text(key)
                    out.append(f"{name}{lt} {_fmt(inst.value)}")
        return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# Process-wide registry + publisher switch
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry()
_PUBLISHING = True


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def set_publishing(enabled: bool) -> None:
    """Switch the always-on publishers (timers, goodput, engine, driver)
    on/off process-wide — the bench_observability.py off-mode."""
    global _PUBLISHING
    _PUBLISHING = bool(enabled)


def publishing() -> bool:
    return _PUBLISHING
