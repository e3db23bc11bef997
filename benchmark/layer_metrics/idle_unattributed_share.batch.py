"""Share of device 0's idle time in the traced span under none of the
scheduler thread's phase spans (admit, plan, launch, fetch, apply, wait): time
directly under `engine-step` or between steps.  Keeps the attribution honest."""

from benchmark.lib import spans

LAYER = 'device'
UNIT = '%'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_span'


def reduce(run):
    return spans.idle_share(run, (None,))
