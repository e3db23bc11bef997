"""Pages of the WINDOW page class a decoding sequence holds, whole window:
mlt_engine_seq_pages_sum{class="window"} (summed over sequences and applied
ticks) over mlt_engine_seq_ticks_total.  ~258 at a window of 4,096 tokens
and pages of 16 however long the context, where a pool with one page class
holds every page of a sequence (~1,090 at these contexts) in every layer.
Lower is better."""

from benchmark.lib import readers

LAYER = 'KV pool and prefix cache generation/engine.py'
UNIT = 'pages'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    pages = readers.counter(run, 'mlt_engine_seq_pages_sum{class="window"}')
    seqs = readers.counter(run, "mlt_engine_seq_ticks_total")
    if pages is None or not seqs:
        return None
    return pages / seqs
