"""Device self time of the ops under the `block_unmask` named scope (the
samples and their confidence over the vocabulary on a block's denoise rows,
the choice of the positions that take theirs, the slot's block state:
generation/blocks.py) plus the head on those rows (`lm_head_loss`), over the
device's busy time in the traced span.  A program without the `block_unmask`
scope reports nothing."""

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    unmask = readers.scope_share(run, "block_unmask")
    if not unmask:
        return None
    return unmask + (readers.scope_share(run, "lm_head_loss") or 0.0)
