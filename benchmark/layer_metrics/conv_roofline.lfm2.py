"""The gated short-convolution mixers' share of their (bandwidth) roofline
over the traced span: the bytes the span's ticks needed (lib/flops_lfm2.py,
from the configuration's keys: both projections' weights and the filter
once a tick and conv layer, each live row in and out, a run's tail read once
and written once) over the HBM peak, over the device self time under the
scope `short_conv`.  The same count whatever implements the mixer.

Ticks: the executions of the engine's tick program in the capture, less one
for the two it cuts.  Runs and rows as `ssm_roofline.nemotron` counts them:
every token a client received in the span was one decode row, a run of its
own; a prompt being prefilled in the span is one run a tick of at most
`prefill_rows` rows (the engine's cap: its slots in whole chunks), by the
share of that prefill that fell in the span (two prompts that share a
tick's rows are two runs and count as the runs of one: the bytes are then
counted too LOW, never too high).  A program without the scope reports
nothing."""

import re

from benchmark.lib import flops_lfm2, readers

LAYER = 'short-conv mixer models/sublayers.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def span_runs(run):
    """(runs, rows) of the conv layers in the traced span."""
    a, b = run.trace_host
    chunk = int(run.engine.get("prefill_chunk") or 64)
    slots = int(run.engine.get("max_slots") or chunk)
    per_tick = -(-slots // chunk) * chunk     # the engine's prefill cap
    runs = rows = 0.0
    for s in run.all_samples:
        got = sum(1 for ts in s["token_t"] if a <= ts <= b)
        runs += got
        rows += got
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is not None and first is not None and first > sent:
            overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
            prompt_rows = max(0, s["n_prompt"] - 1)   # the last is decoded
            runs += overlap * -(-prompt_rows // per_tick)
            rows += overlap * prompt_rows
    return runs, rows


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "conv_L_cache" not in run.cell.model or not run.trace.devices:
        return None
    t = run.trace.self_seconds(
        lambda o: "/short_conv/" in o.op_name + "/")
    tick = re.compile(readers.TICK_PROGRAM)
    ticks = sum(1 for name, _, _ in run.trace.devices[0].modules
                if tick.search(name)) - 1
    if t <= 0 or ticks <= 0:
        return None
    runs, rows = span_runs(run)
    need = flops_lfm2.mixer_bytes(run.cell.model, ticks, runs, rows)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: short-conv mixers: {t * 1e3:.2f} ms under short_conv "
          f"in the traced span, {ticks} ticks, {runs:.0f} runs of "
          f"{rows:.0f} rows, {need / 1e9:.3f} GB of weights, rows and tails "
          f"needed, least {least * 1e3:.2f} ms (bandwidth-bound)", flush=True)
    return 100.0 * least / t
