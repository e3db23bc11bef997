"""The flash forward + backward kernels' share of their roofline over the whole
train steps in the trace (lib/readers.py flash_roofline prints which bound
holds)."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/flash_attention.py'
UNIT = '%'
MOVES = 'train_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    return readers.flash_roofline(run)
