"""The flash forward + backward kernels' share of their roofline under BOTH
masks, over the whole train steps in the trace: the FLOPs and bytes each
layer's own mask needs (lib/flops_hybrid.py flash_train_cost: full causal in
the global layers, at most 4096 keys a query in the window layers) at the
chip's peaks, over the device time of the kernels named `flash_fwd`,
`flash_bwd_dq`, `flash_bwd_dkv` (this step also runs the grouped-matmul Pallas
kernels, so kernels are picked by name).  Compute-bound at these shapes; a
forward re-run by rematerialisation adds time and no needed work, so the
share stays under 100%."""

from benchmark.lib import flops, flops_hybrid, readers, train_spans

LAYER = 'kernels ops/pallas/flash_attention.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None:
        return None
    steps = run.trace.full_runs(readers.TRAIN_PROGRAM)
    t = run.trace.self_seconds_within(train_spans.is_flash, steps)
    if not steps or t <= 0:
        return None
    seq = int(run.cell.traffic["seq_length"])
    seqs = run.tokens_per_step // seq * len(steps)
    cost = flops_hybrid.flash_train_cost(run.cell.model, seq, seqs)
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                          run.peaks)
    print(f"benchmark: flash kernels, two masks: {len(steps)} whole steps, "
          f"{t * 1e3:.2f} ms of kernel time, least {least * 1e3:.2f} ms "
          f"({bound}-bound)", flush=True)
    return 100.0 * least / t
