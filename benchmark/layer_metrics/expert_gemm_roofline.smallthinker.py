"""The grouped expert GEMMs' share of their roofline over the whole train
steps of the traced span: the FLOPs and bytes the HELD rows' three GEMMs
need, forward and both gradients (lib/flops_hybrid.py expert_gemm_train_cost),
at the chip's peaks, over the device self time under the scope
`moe/expert_gemm` in those steps.  The rows are the `held=` of the program's
`train-moe` spans in the capture (their mean a step: every step routes a
batch of the same size).  Compute-bound at ~1,500 rows an expert; the
recomputed forward adds time and no needed work, so the share stays under
100%."""

from benchmark.lib import flops, flops_hybrid, readers, train_spans

LAYER = 'expert layer models/moe.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or run.peaks is None:
        return None
    noted = train_spans.moe_spans(run)
    steps = run.trace.full_runs(readers.TRAIN_PROGRAM)
    t = run.trace.self_seconds_within(
        lambda o: "/moe/expert_gemm/" in o.op_name + "/", steps)
    if not noted or not steps or t <= 0:
        return None
    held = sum(float(s.args["held"]) for s in noted) / len(noted)
    dropped = sum(float(s.args.get("dropped", 0)) for s in noted)
    cost = flops_hybrid.expert_gemm_train_cost(
        run.cell.model, held * len(steps), len(steps))
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                          run.peaks)
    print(f"benchmark: expert GEMMs: {len(steps)} whole steps, {held:.0f} "
          f"held rows a step ({len(noted)} train-moe spans, {dropped:.0f} "
          f"dropped for want of a row), "
          f"{cost['flops'] / 1e12:.3f} TFLOP and {cost['bytes'] / 1e9:.3f} GB "
          f"needed, least {least * 1e3:.2f} ms ({bound}-bound), "
          f"{t * 1e3:.2f} ms under moe/expert_gemm", flush=True)
    return 100.0 * least / t
