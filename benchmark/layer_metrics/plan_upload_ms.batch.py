"""The slot-state upload of the plan phase, in ms a tick over the whole
window: the sum of mlt_engine_plan_part_seconds for part="upload" (the span
plan-upload: the block tables, positions, tokens, keys, steps and sampling
parameters to the device whenever admission or retirement dirtied them, and
the carry mask) over mlt_engine_ticks_total.  Part of host_work_ms.batch's
plan."""

from benchmark.lib import per_tick

LAYER = 'engine host between launches generation/engine.py'
UNIT = 'ms'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    return per_tick.ms(
        run, ['mlt_engine_plan_part_seconds_sum{part="upload"}'])
