"""Positions that took their sample a denoising step, whole window:
mlt_engine_block_tokens_unmasked_total over mlt_engine_block_steps_total
(slot-steps).  1.0 under `sequential` at 4 steps a block of 4, and under the
published `low_confidence_dynamic` too while no confidence passes its
threshold (random weights); a trained model reads higher, and its tokens a
second rise with it.  A program without the counters reports nothing."""

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = 'tokens'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    unmasked = readers.counter(run, "mlt_engine_block_tokens_unmasked_total")
    steps = readers.counter(run, "mlt_engine_block_steps_total")
    if not unmasked or not steps:
        return None
    return unmasked / steps
