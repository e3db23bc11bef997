"""Wall time the pool's two scans cost, in ms a tick over the whole window:
mlt_engine_pool_scan_seconds_total for what="evictable" (counting the cached
pages no request references: every alloc past the free list, every admission
check) plus what="evict" (the prefix cache picking victims, a pass over the
trie each) over mlt_engine_ticks_total.  Pure Python under the engine's lock,
on the scheduler's thread but for a /health answer."""

from benchmark.lib import per_tick

LAYER = 'KV pool and prefix cache generation/engine.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    return per_tick.ms(run, [
        'mlt_engine_pool_scan_seconds_total{what="%s"}' % what
        for what in ("evictable", "evict")])
