"""Ticks in which a slot had live block rows
(mlt_engine_block_slot_ticks_total) over blocks committed
(mlt_engine_blocks_committed_total), whole window: what `tick_ms.batch` is
multiplied by to give a slot's seconds a block.  4 with the commit rows
riding on the next block's first step at one token a step; 5 with a commit
tick of its own.  A request's last block is never committed (nothing reads
its K/V), so the window reads a little over 4.  A program without the
counters reports nothing."""

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = 'ticks'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    ticks = readers.counter(run, "mlt_engine_block_slot_ticks_total")
    blocks = readers.counter(run, "mlt_engine_blocks_committed_total")
    if not ticks or not blocks:
        return None
    return ticks / blocks
