"""Process start to window open: imports, weights, compile or cache load,
warm-up, ramp. Seconds on the host clock."""

UNIT = 's'
SOURCE = 'host_clock'


def reduce(run):
    return run.setup_s
