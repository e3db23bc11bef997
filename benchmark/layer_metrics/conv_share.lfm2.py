"""Device self time of the ops under the `short_conv` named scope (a gated
short-convolution layer's in-projection and gate, its convolution on the
tick's rows and tails, the second gate and the out-projection, every conv
layer: models/sublayers.py `short_conv_sublayer`) over the device's busy
time in the traced span.  A program without the scope reports nothing."""

from benchmark.lib import readers

LAYER = 'short-conv mixer models/sublayers.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, "short_conv")
    return share if share else None
