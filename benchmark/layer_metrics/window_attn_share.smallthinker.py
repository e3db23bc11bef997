"""Device time of the flash kernels (`flash_fwd`, `flash_bwd_dq`,
`flash_bwd_dkv`) that run under the scope `attention/window` over the device
time of all flash kernels, over the whole train steps in the trace.  Three of
four layers are window layers: if the kernels' work follows the mask they
take 3 x 3584 of 3 x 3584 + 8192 mean keys a query = 57%, if a kernel does
not skip the blocks behind the window 75%.  A share of a sum: at most 100%."""

from benchmark.lib import readers, train_spans

LAYER = 'kernels ops/pallas/flash_attention.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None:
        return None
    steps = run.trace.full_runs(readers.TRAIN_PROGRAM)
    every = run.trace.self_seconds_within(train_spans.is_flash, steps)
    window = run.trace.self_seconds_within(
        lambda o: train_spans.is_flash(o)
        and "/attention/window/" in o.op_name + "/", steps)
    if not steps or every <= 0 or window <= 0:
        return None     # a program without the scope reports nothing
    return 100.0 * window / every
