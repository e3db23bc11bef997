"""Device self time of the `paged_attention` kernel events (192 calls a
tick in a looped stack: a call a layer and pass) over the device's busy time
in the traced span.  A capture without the kernel reports nothing."""

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    if "total_ut_steps" not in run.cell.model:
        return None
    t = run.trace.self_seconds(
        lambda o: o.is_pallas and "paged_attention" in o.name)
    return 100.0 * t / run.trace.busy_s if t > 0 else None
