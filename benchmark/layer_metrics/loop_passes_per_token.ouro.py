"""Passes of the stack the DEVICE ran for a tick's tokens, over the whole
ticks of the traced span: the executions of the paged attention kernel under
the `loop_pass` named scope (one a layer application: the kernel is called
once a layer and pass) over the whole ticks, over ``num_hidden_layers``.
Every token of a tick passes every layer application of it, so this is the
passes a token took.  Counted from what ran, not from a configured number:
a pass loop that stopped early, or a layer scan that skipped a layer, reads
under ``total_ut_steps`` (4.0 at the published ``early_exit_threshold`` of
1.0, which runs every token through every pass), so higher is better until
an exit below 1 makes the count the data's.  A whole tick is an execution of
the tick program that the capture did not cut: every one but the first and
the last.  A program without the scope reports nothing."""

import re

from benchmark.lib import hlo_modules, readers

LAYER = 'engine tick generation/ragged.py'
UNIT = 'passes'
MOVES = 'decode_tokens_per_s'
SOURCE = 'device_trace'


def reduce(run):
    if run.trace is None or not run.trace.devices or not run.trace.path:
        return None
    if "total_ut_steps" not in run.cell.model:
        return None
    rx = re.compile(readers.TICK_PROGRAM)
    ops = hlo_modules.Attributed(run.trace, hlo_modules.read(run.trace.path))
    ticks = {r for r in ops.runs if rx.search(r[2])}
    ticks -= {min(ticks, default=None), max(ticks, default=None)}
    calls = 0
    for o in ops.ops:
        if not (o.is_pallas and "paged_attention" in o.name):
            continue
        ins = ops.instruction(o)
        if ins is not None and ops.run_of(o) in ticks and (
                "/loop_pass/" in ins.op_name + "/"):
            calls += 1
    if not ticks or not calls:
        return None
    return calls / len(ticks) / int(run.cell.model["num_hidden_layers"])
