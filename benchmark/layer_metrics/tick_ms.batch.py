"""Median device duration of one whole execution of the engine's tick program
in the trace."""

from benchmark.lib import readers

LAYER = 'engine tick generation/ragged.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    return readers.program_median_ms(run, readers.TICK_PROGRAM)
