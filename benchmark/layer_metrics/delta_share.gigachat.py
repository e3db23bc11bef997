"""Device self time of the ops under the `attention/delta` named scope (a
linear layer's projections, its convolution, the `delta_sweep` kernel, the
gated head norm and the output projection, every linear layer) over the
device's busy time in the traced span.  A program without the scope
reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/gated_delta.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, "attention/delta")
    return share if share else None
