"""Denoise and commit rows the block tick ran per token it appended to its
requests' streams, whole window: (mlt_engine_block_denoise_rows_total +
mlt_engine_block_commit_rows_total) over mlt_engine_ticked_tokens_total,
all three counted by the program at the same applied tick (the tokens the
CLIENTS received in the window lag the ticks by whatever the host is doing,
a profiler's capture included: the first traced runs read 5.0, 5.9 and 7.6
at captures of 1, 2 and 3 s that way).  A whole block at one token a step is
4 x 4 denoise rows + 4 commit rows for 4 tokens: 5.0; a block that opens on
prompt tokens takes fewer steps and a request's last block commits nothing,
so the window reads a little off it.  Lower is better: a trained model that
unmasks several tokens a step reads under 5.  A program without the
counters reports nothing."""

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    denoise = readers.counter(run, "mlt_engine_block_denoise_rows_total")
    commit = readers.counter(run, "mlt_engine_block_commit_rows_total")
    tokens = readers.counter(run, "mlt_engine_ticked_tokens_total")
    if not denoise or commit is None or not tokens:
        return None
    return (denoise + commit) / tokens
