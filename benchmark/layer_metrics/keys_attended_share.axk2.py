"""Latent rows the selecting rows attended over the index keys their
indexer scored, whole window, in %: mlt_engine_sparse_keys_attended_total
over mlt_engine_sparse_keys_scored_total (rows past index_topk keys only; a
shorter row attends all it has and is in neither).  At a 33k context and
index_topk 2,048 about 6%: what the selection saves the attention.  A
program without the counters reports nothing."""

from benchmark.lib import readers

LAYER = 'learned sparse attention ops/sparse_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    scored = readers.counter(run, "mlt_engine_sparse_keys_scored_total")
    attended = readers.counter(run, "mlt_engine_sparse_keys_attended_total")
    if not scored or attended is None:
        return None
    return 100.0 * attended / scored
