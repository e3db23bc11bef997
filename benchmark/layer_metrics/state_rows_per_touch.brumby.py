"""Rows the state sweep served a touch of a state, whole window: the live
rows of the launched ticks (mlt_engine_state_rows_total: decode and prompt
rows) over the state reads-and-writes they cost a layer and KV head
(mlt_engine_state_touches_total: ONE a run, a sequence's consecutive rows of
one tick), both counted on the host from each tick's plan by the program's
own rule (ops/retention.tick_runs).  1.0 for decode-only ticks; a tick of
39 decode rows and one 64-row prompt run reads 103 / 40 = 2.6.  A program
that has no such counters reports nothing."""

from benchmark.lib import readers

LAYER = 'kernels ops/pallas/retention.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    rows = readers.counter(run, "mlt_engine_state_rows_total")
    touches = readers.counter(run, "mlt_engine_state_touches_total")
    if not rows or not touches:
        return None
    return rows / touches
