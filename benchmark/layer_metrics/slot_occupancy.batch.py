"""Decode rows per tick over max_batch_slots: tokens sampled in the window
(mlt_engine_ticked_tokens_total) over ticks run (mlt_engine_ticks_total)
over the slots."""

from benchmark.lib import readers

LAYER = 'engine scheduler generation/engine.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    ticks = readers.counter(run, "mlt_engine_ticks_total")
    toks = readers.counter(run, "mlt_engine_ticked_tokens_total")
    slots = run.engine.get("max_slots")
    if not ticks or toks is None or not slots:
        return None
    return 100.0 * toks / ticks / slots
