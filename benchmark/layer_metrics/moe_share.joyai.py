"""Device self time of the operations under the named scope `moe`
(models/moe.py: router, dispatch, grouped expert GEMMs, combine, shared
expert) over device busy time."""

from benchmark.lib import readers

LAYER = 'expert layer models/moe.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, 'moe')
    return share or None      # a program without the scope reports nothing
