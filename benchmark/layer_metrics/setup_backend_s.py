"""Process start to the installation of the program's compile log, which
``enable_compilation_cache()`` makes right after ``jax.devices()``: imports,
jax's start, the chip's claim."""

from benchmark.lib import startup

LAYER = 'start-up utils/platform.py observability/compiles.py'
UNIT = 's'
MOVES = 'setup_s'
SOURCE = 'program_counter'


def reduce(run):
    return startup.backend_s(run)
