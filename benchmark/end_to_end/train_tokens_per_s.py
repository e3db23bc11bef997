"""Tokens of the optimizer steps completed in the window over the window's
length, all chips together (lib/kind_train.py says how the window is
fenced)."""

from benchmark.lib import readers

UNIT = 'tokens/s'
SOURCE = 'host_clock'


def reduce(run):
    return readers.train_rate(run)
