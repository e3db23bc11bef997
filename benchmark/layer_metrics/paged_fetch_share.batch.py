"""Share of their compute blocks the paged kernel fetched for a tick's rows,
whole window: the blocks its page walks read
(mlt_engine_paged_blocks_fetched_total) over the blocks under the live
rows' masks summed over rows (mlt_engine_paged_blocks_seen_total), both
times the attention layers of the rows' page class and both counted on the
host from each tick's plan by the kernel's own rule
(ops/pallas/paged_attention.tile_shares): a run's blocks are fetched once a
tile of 8 rows, the blocks that rows of one tile name alike (sequences on
one cached prefix, laid side by side by the tick) once a span, every other
block once a row.  100 is a kernel that walks every row alone; a tick of
55 decode rows and 64 prompt rows of ~134 blocks each reads 54 where the
runs alone save, and 15-35 where the decode rows stand on four shared
prefixes.  A program that has no such counters (every block was fetched a
row, save the runs') reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    seen = readers.counter(run, "mlt_engine_paged_blocks_seen_total")
    fetched = readers.counter(run, "mlt_engine_paged_blocks_fetched_total")
    if not seen or not fetched:
        return None
    return 100.0 * fetched / seen
