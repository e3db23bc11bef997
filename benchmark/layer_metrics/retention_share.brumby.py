"""Device self time of the ops under the `retention` named scope (the
feature map's GEMMs, the state sweep's kernel, the head norms, RoPE and the
gate of every layer) over the device's busy time in the traced span.  A
program without the scope reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/retention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, "retention")
    return share if share else None
