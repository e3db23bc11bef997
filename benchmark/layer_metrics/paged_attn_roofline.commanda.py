"""The paged attention kernel's share of its bandwidth roofline over the
traced span, with the needed bytes counted under each layer's OWN mask
(lib/flops_commanda.py): for every token a client received in the span the
keys its query could see, min(context, 4096) in a window layer and all of
them in a full layer; plus, for every prompt being prefilled in the span,
the keys each of its chunks needed once, FROM the shared prefix's cached
pages on (what the prefix cache served is not prefilled), by the share of
that request's prefill that fell in the span.  Over the device time of the
`paged_attention` kernel events.  `paged_attn_roofline.batch` counts every
key in every layer and a prompt's chunks from position 0: both too many
here."""

from benchmark.lib import flops_commanda

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def needed_bytes(model, samples, span, chunk, page, prefix_tokens):
    """K/V bytes the span's rows needed: (total, of which window layers)."""
    a, b = span
    total = window = 0.0

    def add(context, weight=1.0):
        nonlocal total, window
        need = flops_commanda.visible_key_bytes(model, context)
        total += weight * (need["window"] + need["full"])
        window += weight * need["window"]

    for s in samples:
        n_prompt = s["n_prompt"]
        for i, ts in enumerate(s["token_t"]):
            if a <= ts <= b:
                add(n_prompt + i)
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is None or first is None or first <= sent:
            continue
        overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
        if overlap <= 0:
            continue
        # the cache serves a primed prefix's whole pages but the last
        hit = 0
        if s.get("prefix") is not None and prefix_tokens:
            hit = (min(prefix_tokens, n_prompt) - 1) // page * page
        for end in range(hit + chunk, n_prompt + chunk, chunk):
            add(min(end, n_prompt), overlap)
    return total, window


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "layer_types" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    shared = run.cell.traffic.get("shared_prefix") or {}
    total, window = needed_bytes(
        run.cell.model, run.all_samples, run.trace_host,
        int(run.engine.get("prefill_chunk") or 64),
        int(run.engine.get("page_size") or 16), int(shared.get("tokens", 0)))
    least = total / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel under two masks: {t * 1e3:.2f} ms of "
          f"kernel time in the traced span, {total / 1e9:.3f} GB of keys and "
          f"values needed ({100 * window / max(total, 1):.1f}% of them by the "
          f"window layers), least {least * 1e3:.2f} ms (bandwidth-bound)",
          flush=True)
    return 100.0 * least / t
