"""Output tokens the clients received inside the window over the window's
length."""

from benchmark.lib import readers

UNIT = 'tokens/s'
SOURCE = 'host_clock'


def reduce(run):
    if run.window_s is None or not run.samples:
        return None
    return readers.tokens_in_window(run) / run.window_s
