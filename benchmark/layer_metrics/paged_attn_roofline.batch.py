"""The paged attention kernel's share of its (bandwidth) roofline over the
traced span (lib/readers.py paged_roofline)."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/paged_attention.py'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    return readers.paged_roofline(run)
