"""Seconds of every backend compile that ended before the window opened,
loads from the persistent cache included, by the program's compile log.
Seconds on the compiling threads: two threads compiling at once add up."""

from benchmark.lib import startup

LAYER = 'start-up utils/platform.py observability/compiles.py'
UNIT = 's'
MOVES = 'setup_s'
SOURCE = 'program_counter'


def reduce(run):
    return startup.stage_s(run, "compile")
