"""The paged attention kernel's share of its (bandwidth) roofline over the
traced span, counted as `paged_attn_roofline.batch` counts
(lib/readers.py paged_roofline) but over the layers that ARE attention, ten
of forty (lib/flops_lfm2.py: 2 x 8 KV heads x 64 bf16 values a token and
attention layer; `lib/flops.kv_bytes_per_token` would count all forty).
Needed bytes: for every token a client received in the span, the keys its
query could see (its context so far) x K and V x attention layers; plus,
for every prompt being prefilled in the span, its keys so far once per
chunk, by the share of that request's prefill that fell in the span.  Over
the device time of the `paged_attention` kernel events.  The mix shares no
prefix, so no block is read for several rows at once (PERF.md 7gg)."""

from benchmark.lib import flops_lfm2

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def needed_keys(samples, span, chunk: int) -> float:
    """Cached tokens the span's rows had to read, summed over rows."""
    a, b = span
    keys = 0.0
    for s in samples:
        n_prompt = s["n_prompt"]
        keys += sum(n_prompt + i for i, ts in enumerate(s["token_t"])
                    if a <= ts <= b)
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is not None and first is not None and first > sent:
            overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
            keys += overlap * sum(min(e, n_prompt) for e in range(
                chunk, n_prompt + chunk, chunk))
    return keys


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "conv_L_cache" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    keys = needed_keys(run.all_samples, run.trace_host,
                       int(run.engine.get("prefill_chunk") or 64))
    need = keys * flops_lfm2.kv_bytes_per_token(run.cell.model)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel, ten attention layers: {t * 1e3:.2f} ms "
          f"of kernel time in the traced span, {need / 1e9:.3f} GB of keys "
          f"and values needed, least {least * 1e3:.2f} ms (bandwidth-bound)",
          flush=True)
    return 100.0 * least / t
