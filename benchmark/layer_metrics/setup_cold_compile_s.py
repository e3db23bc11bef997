"""Of set-up's compiles, the seconds of those the backend compiled: what the
persistent cache did not answer, whether it was empty, had dropped the entry,
or never keeps a program as cheap as this one (jax writes none that compiled
in under ``jax_persistent_cache_min_compile_time_secs``, so a program asked
for often and compiled fast is compiled at every asking).  ``setup_compile_s``
less this is what the loads took.  Seconds on the compiling threads."""

from benchmark.lib import startup

LAYER = 'start-up utils/platform.py observability/compiles.py'
UNIT = 's'
MOVES = 'setup_s'
SOURCE = 'program_counter'


def reduce(run):
    return startup.cold_compile_s(run)
