"""Share of the window's ticks that paid for an eviction: ticks during whose
admit or plan at least one page grant went through the prefix cache's evict
hook (mlt_engine_pool_dry_ticks_total) over mlt_engine_ticks_total.  0 while
the pool's free list lasts; the share of the window spent in the cell's slow
regime once it does not.  Whole window, so it sees what the traced 3 s in the
window's middle do not."""

from benchmark.lib import readers

LAYER = 'KV pool and prefix cache generation/engine.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    ticks = readers.counter(run, "mlt_engine_ticks_total")
    dry = readers.counter(run, "mlt_engine_pool_dry_ticks_total")
    if not ticks or dry is None:
        return None
    return 100.0 * dry / ticks
