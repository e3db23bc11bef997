"""Device self time of the operations under the named scope `lm_head_loss`
(models/language_model.py: the head and the cross-entropy, forward and
backward) over device busy time."""

from benchmark.lib import readers

LAYER = 'train step training_step.py optimizer/'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, 'lm_head_loss')
    return share or None    # a program without the scope has no such metric
