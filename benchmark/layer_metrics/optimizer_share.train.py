"""Device self time of the operations under the named scope `optimizer`
(training_step.py) over device busy time."""

from benchmark.lib import readers

LAYER = 'train step training_step.py optimizer/'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    return readers.scope_share(run, 'optimizer')
