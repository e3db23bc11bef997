"""Rows the paged kernel served a page walk, whole window: the live rows of
the launched ticks (mlt_engine_paged_rows_total: decode, verify and prompt
rows) over the page walks they cost it a layer
(mlt_engine_paged_walks_total), both counted on the host from each tick's
plan by the kernel's own grouping rule
(ops/pallas/paged_attention.tile_runs): a tile of 8 consecutive rows of one
sequence at consecutive positions, a prompt chunk's, is walked ONCE, with
one matmul a kv head for all of them; any other row, every decode row,
walks its context alone.  1.0 is one walk a row; a tick of 55 decode rows
and 64 prompt rows reads 119 / 63 = 1.9.  A program that has no such
counters (every walk was a row's) reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    rows = readers.counter(run, "mlt_engine_paged_rows_total")
    walks = readers.counter(run, "mlt_engine_paged_walks_total")
    if not rows or not walks:
        return None
    return rows / walks
