"""``state_rows_per_step.nemotron`` (PR 54): rows over the sweep's steps
from the program's two counters, nothing where the program has no steps
counter (the parent of PR 54), its entry in the index, and the host rule it
counts by on the cell's own ticks."""

import os
import types

import numpy as np

from benchmark.lib import cells

CELL = "nemotron3_nano_chat_closed"
NAME = "state_rows_per_step.nemotron"


def _reader():
    cell = cells.Cell(CELL)
    return cell, cells.Cell.reader_at(os.path.join(
        cell.bench_dir, "layer_metrics", NAME + ".py"))


def test_rows_over_steps_and_nothing_without_the_counter():
    _, reader = _reader()
    run = types.SimpleNamespace(counters={
        "mlt_engine_state_rows_total": 9600.0,
        "mlt_engine_state_touches_total": 3300.0,
        "mlt_engine_state_steps_total": 3400.0})
    assert reader.reduce(run) == 9600.0 / 3400.0
    del run.counters["mlt_engine_state_steps_total"]
    assert reader.reduce(run) is None
    assert reader.reduce(types.SimpleNamespace(counters={})) is None


def test_the_entry_is_the_readers_and_the_cells_alone():
    """Wherever later PRs' entries come to stand beside it."""
    cell, reader = _reader()
    (entry,) = (m for m in cell.bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "higher",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": [CELL]}
    assert entry["layer"] in {m["layer"] for m in cell.bench["per_layer"]
                              if m["name"] != NAME}
    assert NAME in {m["name"] for m in cell.per_layer}


def test_the_hosts_rule_on_the_cells_ticks():
    """32 decode rows and 64 prompt rows behind them, tiles of 32: one
    64-row run is 2 steps, a 24-row and a 40-row run are 3 (24, then 8 and
    32), decode rows one each, a dead row none."""
    from megatron_llm_tpu.ops.mamba2 import sweep_steps

    decode = np.arange(1, 33)
    for runs, want in (((64,), 34), ((24, 40), 35), ((), 32), ((7, 57), 35)):
        slots = np.concatenate(
            [decode] + [np.full(r, 40 + i) for i, r in enumerate(runs)])
        pos = np.concatenate(
            [np.zeros(32, int)] + [128 * i + np.arange(r)
                                   for i, r in enumerate(runs)])
        assert sweep_steps(slots, pos) == want
        slots[3] = 0
        assert sweep_steps(slots, pos) == want - 1
