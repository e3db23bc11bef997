"""The paged attention kernel's share of its (bandwidth) roofline over the
traced span for a model that generates by diffusion over blocks
(lib/flops_sdar.py): the bytes a denoising step NEEDS are its sequence's K/V
ONCE a layer, however many of the block's rows run (4 denoise rows, 4
commit rows of the block before) and however the kernel walks them; one
step a token received (the cell's one token a step); plus a prompt's keys so
far once a chunk.  Over the device time of the `paged_attention` kernel
events.  The same count whatever implements it: a kernel that walks a
block's rows one by one reads under 25, one walk a block nears what
`paged_attn_roofline.batch` reads for a causal model.  Under 100 always."""

from benchmark.lib import flops_sdar

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None or not run.trace_host:
        return None
    if "diffusion_block_length" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    if t <= 0:
        return None
    keys = flops_sdar.needed_keys(
        run.all_samples, run.trace_host,
        int(run.engine.get("prefill_chunk") or 64))
    need = keys * flops_sdar.kv_bytes_per_token(run.cell.model)
    least = need / run.peaks["hbm_bytes_per_s"]
    print(f"benchmark: paged kernel, block rows: {t * 1e3:.2f} ms of kernel "
          f"time in the traced span, {need / 1e9:.3f} GB of keys and values "
          f"needed (a step's sequence once), least {least * 1e3:.2f} ms "
          f"(bandwidth-bound)", flush=True)
    return 100.0 * least / t
