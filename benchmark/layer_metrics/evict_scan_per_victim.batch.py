"""Trie nodes looked at for one evicted page, whole window:
mlt_engine_prefix_evict_scanned_nodes_total over
mlt_engine_prefix_evicted_pages_total.  The eviction's work per victim: the
size of the trie while every victim costs a pass over it, 1 to 2 once victims
come off an ordered structure.  Left out where nothing was evicted."""

from benchmark.lib import readers

LAYER = 'KV pool and prefix cache generation/engine.py'
UNIT = 'nodes'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    scanned = readers.counter(
        run, "mlt_engine_prefix_evict_scanned_nodes_total")
    evicted = readers.counter(run, "mlt_engine_prefix_evicted_pages_total")
    if scanned is None or not evicted:
        return None
    return scanned / evicted
