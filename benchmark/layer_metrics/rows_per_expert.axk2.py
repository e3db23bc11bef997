"""Rows a HELD expert's GEMM ran on, whole window: the router assignments
that fell to the experts this chip holds
(mlt_engine_moe_held_assignments_total) over the distinct held experts that
received a row (mlt_engine_moe_held_experts_touched_total), both summed over
ticks and expert layers.  `rows_per_expert.joyai` divides ALL assignments by
all experts touched, which is the same number only where every expert is
held.  (A.X-K2's cell: 8 held of 256; `rows_per_expert.gigachat` is the same
reading of another cell.)"""

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
