"""Device self time of the ops under the `index_select` named scope (the
exact selection of the index_topk best-scored keys a row: the threshold's
counting passes and the compaction to a list, every attention layer) over
the device's busy time in the traced span.  A program without the scope
reports nothing."""

from benchmark.lib import readers

LAYER = 'learned sparse attention ops/sparse_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    share = readers.scope_share(run, "index_select")
    return share if share else None
