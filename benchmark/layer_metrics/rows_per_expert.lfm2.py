"""Rows a HELD expert's GEMM ran on, whole window: the router assignments
that fell to the experts this chip holds
(mlt_engine_moe_held_assignments_total) over the distinct held experts that
received a row (mlt_engine_moe_held_experts_touched_total), both summed over
ticks and the 38 expert layers, as `rows_per_expert.gigachat` divides them.
With 8 of 64 experts held and top-4, 256 decode rows give a held expert 16
a tick (32 with a full prompt budget), an eighth of the deployment's."""

from benchmark.lib import readers

LAYER = 'expert layer models/moe.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    rows = readers.counter(run, "mlt_engine_moe_held_assignments_total")
    touched = readers.counter(run, "mlt_engine_moe_held_experts_touched_total")
    if not rows or not touched:
        return None
    return rows / touched
