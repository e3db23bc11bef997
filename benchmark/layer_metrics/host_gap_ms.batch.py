"""Mean of mlt_engine_host_gap_seconds over the window: host time between one
tick launch returning and the next being dispatched."""

from benchmark.lib import readers

LAYER = 'engine host between launches generation/engine.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    v = readers.histogram_mean(run, "mlt_engine_host_gap_seconds")
    return None if v is None else 1e3 * v
