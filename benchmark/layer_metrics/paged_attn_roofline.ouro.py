"""The paged attention kernel's share of its (bandwidth) roofline over the
traced span for a looped stack (lib/flops_ouro.py): a cached token holds a
K/V row a layer AND pass (192 slots, 1.5 MiB a token), and the 192 calls of
a tick each read their own slot of every key a row sees.  Needed bytes: for
every token a client received in the span its context so far x the bytes a
token holds over all slots, each DISTINCT page once (nothing is shared
between this mix's sequences; a prompt chunk's rows share one walk of their
sequence's prefix, counted once a chunk), over the HBM peak, over the device
time of the `paged_attention` kernel events.  Under 100 always."""

from benchmark.lib import flops_ouro

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "total_ut_steps" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    keys = flops_ouro.needed_keys(
        run.all_samples, run.trace_host,
        int(run.engine.get("prefill_chunk") or 64))
    need = keys * flops_ouro.kv_bytes_per_token(run.cell.model)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel, looped stack: {t * 1e3:.2f} ms of "
          f"kernel time in the traced span, {need / 1e9:.3f} GB of keys and "
          f"values needed over {flops_ouro.cache_layer_slots(run.cell.model)}"
          f" layer slots (distinct pages once), least {least * 1e3:.2f} ms "
          f"(bandwidth-bound)", flush=True)
    return 100.0 * least / t
