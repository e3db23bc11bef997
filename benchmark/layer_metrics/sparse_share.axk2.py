"""Device self time of the ops under the four scopes of learned sparse
attention (`index_score`, `index_select`, `sparse_gather`,
`sparse_attention`: the sweep over the index keys, the selection, the
gather of the picked latent rows and the attention over them, every
attention layer) over the device's busy time in the traced span: the share
of a tick that the mechanism takes.  A program without the scopes reports
nothing."""

from benchmark.lib import readers

LAYER = 'learned sparse attention ops/sparse_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'

SCOPES = ("index_score", "index_select", "sparse_gather", "sparse_attention")


def reduce(run):
    shares = [readers.scope_share(run, s) for s in SCOPES]
    if not any(shares):
        return None
    return sum(s or 0.0 for s in shares)
