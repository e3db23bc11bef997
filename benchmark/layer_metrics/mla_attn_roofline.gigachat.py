"""The paged kernel's share of its (bandwidth) roofline under latent
attention in a HYBRID stack, over the traced span: the latent rows the
span's queries had to read in the layers that are latent attention
(lib/flops_delta.py: ONE layer in five here; `mla_attn_roofline.joyai`
prices every layer of the stack) over the HBM peak, over the device time of
the `paged_attention` kernel events.  Needed rows as that reader counts
them: for every token a client received in the span the context so far,
and for every prompt being prefilled in the span its cached prefix once
per chunk, by the share of that prefill that fell in the span."""

from benchmark.lib import flops_delta

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "full_attention_layers" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    a, b = run.trace_host
    chunk = int(run.engine.get("prefill_chunk") or 64)
    keys = 0.0
    for s in run.all_samples:
        n_prompt = s["n_prompt"]
        keys += sum(n_prompt + i for i, ts in enumerate(s["token_t"])
                    if a <= ts <= b)
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is not None and first is not None and first > sent:
            overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
            if overlap > 0:
                keys += overlap * sum(
                    min(e, n_prompt)
                    for e in range(chunk, n_prompt + chunk, chunk))
    need = keys * flops_delta.latent_bytes_per_token(run.cell.model)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel (latent, hybrid): {t * 1e3:.2f} ms of "
          f"kernel time in the traced span, {need / 1e9:.3f} GB of latent "
          f"rows needed, least {least * 1e3:.2f} ms (bandwidth-bound)",
          flush=True)
    return 100.0 * least / t
