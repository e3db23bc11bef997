"""Arithmetic the metric readers share.  Each metric still has a file of
its own (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) that says
what it is and calls one of these; a later PR adds a metric by adding such
a file, with its own arithmetic if none here fits."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.lib import flops, stats

TICK_PROGRAM = r"^jit_tick\("        # the engine's ragged tick, as jitted
TRAIN_PROGRAM = r"^jit_train_step\("
ABANDONED = "abandoned at the window's end"   # as lib/client.py


# ---- serving samples -------------------------------------------------------

def ok_samples(run) -> List[Dict]:
    return [s for s in run.samples if s["status"] == 200 and s["token_t"]
            and (not s["error"] or s["error"] == ABANDONED)]


def failed_samples(run) -> List[Dict]:
    return [s for s in run.samples if s.get("error") != ABANDONED
            and (s["status"] != 200 or s["error"])]


def token_gaps(run) -> List[float]:
    return [b - a for s in ok_samples(run)
            for a, b in zip(s["token_t"], s["token_t"][1:])]


def tokens_in_window(run) -> int:
    return sum(1 for s in ok_samples(run) for t in s["token_t"]
               if run.t_open <= t <= run.t_close)


# ---- program counters ------------------------------------------------------

def histogram_mean(run, name: str) -> Optional[float]:
    """Mean of a Prometheus histogram over the window (sum and count are
    exact; the buckets of these histograms are too coarse for a median)."""
    n = run.counters.get(name + "_count", 0.0)
    return run.counters.get(name + "_sum", 0.0) / n if n else None


def counter(run, name: str) -> Optional[float]:
    return run.counters.get(name)


# ---- trace -----------------------------------------------------------------

def program_median_ms(run, pattern: str) -> Optional[float]:
    if run.trace is None:
        return None
    runs = run.trace.module_durations(pattern)
    if len(runs) > 4:       # the capture cuts the first and the last
        runs = runs[1:-1]
    return 1e3 * stats.median(runs) if runs else None


def scope_share(run, scope: str) -> Optional[float]:
    """Device self time of the ops under a jax named scope over device
    busy time, in %."""
    if run.trace is None or not run.trace.busy_s:
        return None
    t = run.trace.self_seconds(lambda o: f"/{scope}/" in o.op_name + "/")
    return 100.0 * t / run.trace.busy_s


def train_rate(run) -> Optional[float]:
    if run.first_window_draw is None or run.last_window_draw is None:
        return None
    steps = run.last_window_draw - run.first_window_draw
    return steps * run.tokens_per_step / (run.t_close - run.t_open)


def layout(run) -> Dict[str, int]:
    f = run.cell.config["flags"]
    tp = int(f.get("tensor_model_parallel_size", 1))
    pp = int(f.get("pipeline_model_parallel_size", 1))
    dp = int(f.get("data_parallel_size", 0)) or max(run.chips // (tp * pp), 1)
    return {"tp": tp, "pp": pp, "dp": dp}


def flash_roofline(run) -> Optional[float]:
    """Least time the chip could take for the flash forward + backward of
    the whole train steps in the trace, over the device time of the flash
    kernel events in those steps (every Pallas call of the train step is
    a flash kernel; a forward re-run by rematerialisation adds time and no
    needed work).  Per device: heads are split by tp, sequences by dp."""
    if run.trace is None or run.peaks is None:
        return None
    steps = run.trace.full_runs(TRAIN_PROGRAM)
    t = run.trace.self_seconds_within(lambda o: o.is_pallas, steps)
    if not steps or t <= 0:
        return None
    mix, lay = run.cell.traffic, layout(run)
    seqs = run.tokens_per_step // int(mix["seq_length"]) / lay["dp"]
    cost = flops.flash_train_cost(run.cell.model, int(mix["seq_length"]), 1)
    need_flops = cost["flops"] * seqs * len(steps) / lay["tp"]
    need_bytes = cost["bytes"] * seqs * len(steps) / lay["tp"]
    least, bound = flops.roofline_seconds(need_flops, need_bytes, run.peaks)
    print(f"benchmark: flash kernels: {len(steps)} whole steps, {t * 1e3:.2f} ms "
          f"of kernel time, least {least * 1e3:.2f} ms ({bound}-bound)", flush=True)
    return 100.0 * least / t


def paged_roofline(run) -> Optional[float]:
    """KV bytes the ticks of the traced span had to read, over the HBM
    peak, over the device time of the ``paged_attention`` kernel events.
    Bandwidth-bound by construction: one query row per cached token does
    2 FLOPs a byte.  Needed bytes: for every token a client received in
    the span, the keys its query could see (context so far, capped at the
    window) x K and V x layers; plus, for every prompt being prefilled in
    the span, its cached prefix once per chunk (a chunk needs its keys
    once, however often the kernel walks them), by the share of that
    request's prefill that fell in the span."""
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    a, b = run.trace_host
    model = run.cell.model
    window = model.get("sliding_window")
    per_token = flops.kv_bytes_per_token(model)
    chunk = int(run.engine.get("prefill_chunk") or 64)
    keys = 0.0
    for s in run.all_samples:
        n_prompt = s["n_prompt"]
        for i, ts in enumerate(s["token_t"]):
            if a <= ts <= b:
                keys += flops.visible_keys(n_prompt + i, window)
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is not None and first is not None and first > sent:
            overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
            if overlap > 0:
                whole = sum(flops.visible_keys(min(e, n_prompt), window)
                            for e in range(chunk, n_prompt + chunk, chunk))
                keys += overlap * whole
    least = keys * per_token / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel: {t * 1e3:.2f} ms of kernel time in the "
          f"traced span, {keys * per_token / 1e9:.3f} GB of keys and values "
          f"needed, least {least * 1e3:.2f} ms (bandwidth-bound)", flush=True)
    return 100.0 * least / t
