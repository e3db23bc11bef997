"""Bench-trajectory drift detector — prints ONE JSON line for the driver.

This tool is the trajectory-level check over the committed CPU-sanity
bench rounds: it loads every ``BENCH_r*.json`` capture (the
round records, ``{"n": .., "parsed": {..}}``), orders them by round,
computes per-metric drift — step time, compile time, tokens/sec —
against the earliest round, and emits a one-line JSON verdict with
configurable thresholds.  The committed ``BENCH_*_cpu_sanity.json``
contract lines ride along as an inventory of current per-subsystem
snapshots (single points — no trajectory yet), so the next regression
has a baseline the day it lands.

History (ROADMAP item 3, closed by ISSUE 15): the r02 -> r05 trajectory
this tool was built to flag (step 18.4s -> 52.2s, compile 38s -> 100s)
was bisected and root-caused as HOST CONTENTION, not code — the round-5
record was measured while the staged 470M e2e jobs shared the
single-core host (step and compile inflated by the same ~2.1x — the
signature of CPU-time division, never of compile-graph growth, which
moves the two independently); re-measuring the exact r05 tree idle
gives 24.4s/47.6s, matching its neighbors.  BENCH_r06.json is the
clean refresh; since then these thresholds are a STANDING REGRESSION
GATE (tests/test_bench_contract.py pins the verdict at "ok"), and a
tripped threshold means bisect-the-code — after first checking, as
round 5 teaches, what else was running on the host.

Exit codes follow the graftcheck convention: 0 = no drift, 1 = drift
detected (the verdict line IS the evidence), 2 = internal error — drift
is a finding to act on, not a reason to re-run.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# (field, direction) — 'up' = growth is drift, 'down' = decay is drift
METRICS = (
    ("step_time_s", "up"),
    ("compile_time_s", "up"),
    ("tokens_per_sec", "down"),
)

# default drift ceilings: ratio of newest to the earliest committed
# round.  Generous on purpose — single-core hosts are noisy — yet the
# known r02->r05 drift (2.8x step, 2.6x compile) trips them by a wide
# margin, which is the point.
DEFAULT_THRESHOLDS = {
    "step_time_s": 1.5,       # newest may cost up to 1.5x the baseline
    "compile_time_s": 1.5,
    "tokens_per_sec": 0.67,   # newest may drop to 0.67x the baseline
}


def load_trajectory(root: str):
    """The committed BENCH_r*.json rounds, ordered by round number.
    Rounds whose bench crashed (no ``parsed`` payload) are skipped —
    absence of evidence is not drift."""
    rows = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed")
        if not isinstance(parsed, dict) or parsed.get("error"):
            continue
        # the evidence format moved mid-trajectory: early rounds carry
        # the timing fields top-level, the cpu-contract rounds nest the
        # measured numbers under "cpu_sanity" (the headline is zeroed
        # off-TPU by contract) — flatten to one comparable view
        flat = dict(parsed.get("cpu_sanity") or {})
        for k, v in parsed.items():
            if k != "cpu_sanity" and v is not None:
                flat.setdefault(k, v)
        rows.append((int(rec.get("n", m.group(1))), os.path.basename(path),
                     flat))
    rows.sort()
    return rows


def compute_drift(rows, thresholds=None):
    """Per-metric drift of the newest round vs the earliest one that
    carries the metric.  Returns the verdict payload."""
    thresholds = {**DEFAULT_THRESHOLDS, **(thresholds or {})}
    metrics = {}
    drifted = False
    for field, direction in METRICS:
        series = [(n, name, p[field]) for n, name, p in rows
                  if isinstance(p.get(field), (int, float))]
        if len(series) < 2:
            metrics[field] = {"rounds": len(series), "ratio": None,
                              "exceeded": False}
            continue
        first_n, first_src, first = series[0]
        last_n, last_src, last = series[-1]
        ratio = (last / first) if first else None
        thr = thresholds[field]
        exceeded = (ratio is not None
                    and (ratio > thr if direction == "up"
                         else ratio < thr))
        drifted = drifted or exceeded
        metrics[field] = {
            "rounds": len(series),
            "first": {"round": first_n, "source": first_src,
                      "value": first},
            "last": {"round": last_n, "source": last_src, "value": last},
            "ratio": round(ratio, 4) if ratio is not None else None,
            "threshold": thr,
            "direction": direction,
            "exceeded": exceeded,
        }
    return {"verdict": "drift" if drifted else "ok", "metrics": metrics}


def load_snapshots(root: str):
    """One-line inventory of the committed per-subsystem CPU-sanity
    contract lines: metric name + the backend it last ran on.  These are
    single points today; they become trajectories the same way the
    BENCH_r series did, and this inventory is their baseline hook."""
    out = {}
    for path in sorted(glob.glob(os.path.join(root,
                                              "BENCH_*_cpu_sanity.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        out[os.path.basename(path)] = {
            "metric": rec.get("metric"),
            "backend": rec.get("backend"),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="repo root holding the committed BENCH_* evidence")
    ap.add_argument("--max_step_ratio", type=float,
                    default=DEFAULT_THRESHOLDS["step_time_s"])
    ap.add_argument("--max_compile_ratio", type=float,
                    default=DEFAULT_THRESHOLDS["compile_time_s"])
    ap.add_argument("--min_toks_ratio", type=float,
                    default=DEFAULT_THRESHOLDS["tokens_per_sec"])
    args = ap.parse_args(argv)

    try:
        rows = load_trajectory(args.root)
        result = compute_drift(rows, {
            "step_time_s": args.max_step_ratio,
            "compile_time_s": args.max_compile_ratio,
            "tokens_per_sec": args.min_toks_ratio,
        })
        line = {
            "bench_drift": 1,
            "verdict": result["verdict"],
            "rounds": len(rows),
            "metrics": result["metrics"],
            "snapshots": load_snapshots(args.root),
        }
    except Exception as e:  # structured error line, never a traceback
        print(json.dumps({"bench_drift": 1, "verdict": "error",
                          "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        return 2
    print(json.dumps(line), flush=True)
    return 0 if result["verdict"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
