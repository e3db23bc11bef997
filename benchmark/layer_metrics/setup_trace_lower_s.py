"""Seconds spent tracing functions and lowering them to MLIR before the window
opened (Python, Mosaic's lowering of Pallas kernels included; no cache keeps
them, so every process pays them again).  Seconds on the compiling threads."""

from benchmark.lib import startup

LAYER = 'start-up utils/platform.py observability/compiles.py'
UNIT = 's'
MOVES = 'setup_s'
SOURCE = 'program_counter'


def reduce(run):
    return startup.stage_s(run, "trace", "lower")
