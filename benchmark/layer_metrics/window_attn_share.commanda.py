"""Device time of the `paged_attention` kernel events under the scope
`attention/window` over the device time of all of them, in the traced span.
Three of the four layers are window layers: at 16.9k-token contexts their
bytes are 3 x 4096 of 3 x 4096 + 16.9k keys = 42%; a kernel that walked the
pages behind a window would read 75%.  A share of a sum: at most 100%."""

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def is_paged(o):
    return o.is_pallas and "paged_attention" in o.name


def reduce(run):
    if run.trace is None:
        return None
    every = run.trace.self_seconds(is_paged)
    window = run.trace.self_seconds(
        lambda o: is_paged(o) and "/attention/window/" in o.op_name + "/")
    if every <= 0 or window <= 0:
        return None     # a program without the scope reports nothing
    return 100.0 * window / every
