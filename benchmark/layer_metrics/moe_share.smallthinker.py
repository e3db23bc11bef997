"""Device self time of the operations under the named scope `moe`
(models/moe.py: router, dispatch, grouped expert GEMMs, combine; forward,
recomputed forward and backward) over device busy time.  A share of busy
time: at most 100%."""

from benchmark.lib import readers

LAYER = 'expert layer models/moe.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, 'moe')
    return share or None      # a program without the scope reports nothing
