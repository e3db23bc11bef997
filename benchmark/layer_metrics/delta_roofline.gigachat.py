"""The state sweep's share of its (bandwidth) roofline over the traced
span: the bytes the span's runs needed (lib/flops_delta.py: a value head's
`[128, 128]` float32 state read once and written once a run, linear layer
and value head, plus the rows' q, k, v, g, beta) over the HBM peak, over
the device time of the `delta_sweep` kernel events.  Runs as
`retention_roofline.brumby` counts them: every token a client received in
the span was one decode row, a run of its own; a prompt being prefilled in
the span is one run a tick of at most `prefill_rows` rows (the engine's
cap: its slots in whole chunks), by the share of that prefill that fell in
the span.  A program without the kernel reports nothing."""

from benchmark.lib import flops_delta

LAYER = 'kernels ops/pallas/gated_delta.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "linear_num_value_heads" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "delta_sweep" in o.name)
    if t <= 0:
        return None
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
    need = flops_delta.sweep_bytes(run.cell.model, runs, rows)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: delta sweep: {t * 1e3:.2f} ms of kernel time in the "
          f"traced span, {runs:.0f} runs of {rows:.0f} rows, "
          f"{need / 1e9:.3f} GB of state and rows needed, least "
          f"{least * 1e3:.2f} ms (bandwidth-bound)", flush=True)
    return 100.0 * least / t
