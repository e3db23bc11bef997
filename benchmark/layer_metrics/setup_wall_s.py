"""Process start to window open of THIS run, the traced one: the whole that the
other set-up readings are parts of (a traced line carries no end-to-end
metric, and the parts need their whole to say which mode the run met)."""

LAYER = 'start-up utils/platform.py observability/compiles.py'
UNIT = 's'
MOVES = 'setup_s'
SOURCE = 'host_clock'


def reduce(run):
    return run.setup_s
