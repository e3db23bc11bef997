"""Rows an expert's GEMM ran on, whole window: router assignments
(mlt_engine_moe_assignments_total) over distinct experts that received a row
(mlt_engine_moe_experts_touched_total), both summed over ticks and expert
layers.  The regime the grouped GEMM ran in: near 1 it streams a weight
matrix a row."""

from benchmark.lib import readers

LAYER = 'expert layer models/moe.py'
UNIT = 'rows'
MOVES = 'decode_tokens_per_s'
SOURCE = 'program_counter'


def reduce(run):
    rows = readers.counter(run, "mlt_engine_moe_assignments_total")
    touched = readers.counter(run, "mlt_engine_moe_experts_touched_total")
    if not rows or not touched:
        return None
    return rows / touched
