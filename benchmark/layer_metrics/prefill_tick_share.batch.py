"""Share of the window's ticks that carried prefill rows, from
mlt_engine_tick_kind_total.  No better direction of its own: read it beside
slot_occupancy.batch (admission at a chunk a tick is what holds the slots)."""

from benchmark.lib import readers

LAYER = 'engine scheduler generation/engine.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    pre = readers.counter(run, 'mlt_engine_tick_kind_total{kind="prefill"}')
    dec = readers.counter(run, 'mlt_engine_tick_kind_total{kind="decode"}')
    if pre is None or dec is None or pre + dec <= 0:
        return None
    return 100.0 * pre / (pre + dec)
