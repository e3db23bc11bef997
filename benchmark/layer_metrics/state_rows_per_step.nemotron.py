"""Rows the Mamba-2 state sweep served a pass over a resident state, whole
window: the live rows of the launched ticks (mlt_engine_state_rows_total:
decode and prompt rows) over the steps the sweep made of them a layer and
block of heads (mlt_engine_state_steps_total: one a decode row; a prompt
run's tiles, ops/mamba2.sweep_steps, the kernel's own plan counted on the
host from each tick's rows).  1.0 for decode-only ticks and for a sweep that
walks a run row by row; a tick of 32 decode rows and one 64-row prompt run
behind them reads 96 / 34 = 2.8.  A program that has no such counter (the
sweep before PR 54) reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/mamba2.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    rows = readers.counter(run, "mlt_engine_state_rows_total")
    steps = readers.counter(run, "mlt_engine_state_steps_total")
    if not rows or not steps:
        return None
    return rows / steps
