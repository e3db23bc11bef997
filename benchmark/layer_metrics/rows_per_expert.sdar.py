"""Rows a HELD expert's GEMM ran on, whole window: the router assignments
that fell to the experts this chip holds
(mlt_engine_moe_held_assignments_total) over the distinct held experts that
received a row (mlt_engine_moe_held_experts_touched_total), both summed over
ticks and the 48 expert layers, as `rows_per_expert.lfm2` divides them.  With
16 of 128 experts held and top-8, the 256 block rows a decode tick compiles
(32 slots x 8; 128-160 of them live) give a held expert 8-16 a tick, an
eighth of the deployment's."""

from benchmark.lib import readers

LAYER = 'expert layer models/moe.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    if "diffusion_block_length" not in run.cell.model:
        return None
    rows = readers.counter(run, "mlt_engine_moe_held_assignments_total")
    touched = readers.counter(run, "mlt_engine_moe_held_experts_touched_total")
    if not rows or not touched:
        return None
    return rows / touched
