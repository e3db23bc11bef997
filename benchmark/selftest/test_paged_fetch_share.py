"""``paged_fetch_share.batch`` (PR 56): blocks fetched over blocks seen from
the program's two counters, in percent; nothing where the program has no
such counters (the parent of PR 56); its entry in the index, with the five
paged cells; and the host rule it counts by on the agent cell's tick."""

import os
import types

import numpy as np

from benchmark.lib import cells

CELLS = ["falcon7b_batch_decode", "joyai_flash_batch_decode",
         "commanda_plus_agent_16k", "gigachat35_reasoning_closed",
         "nemotron3_nano_chat_closed"]
NAME = "paged_fetch_share.batch"


def _reader(cell=CELLS[2]):
    cell = cells.Cell(cell)
    return cell, cells.Cell.reader_at(os.path.join(
        cell.bench_dir, "layer_metrics", NAME + ".py"))


def test_fetched_over_seen_and_nothing_without_the_counters():
    _, reader = _reader()
    run = types.SimpleNamespace(counters={
        "mlt_engine_paged_rows_total": 119.0,
        "mlt_engine_paged_walks_total": 63.0,
        "mlt_engine_paged_blocks_seen_total": 15545.0,
        "mlt_engine_paged_blocks_fetched_total": 2733.0})
    assert reader.reduce(run) == 100.0 * 2733.0 / 15545.0
    del run.counters["mlt_engine_paged_blocks_fetched_total"]
    assert reader.reduce(run) is None
    assert reader.reduce(types.SimpleNamespace(counters={})) is None


def test_the_entry_is_the_readers_and_the_paged_cells():
    """Wherever later PRs' entries come to stand beside it; every named cell
    reports it."""
    cell, reader = _reader()
    (entry,) = (m for m in cell.bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": CELLS}
    assert entry["layer"] in {m["layer"] for m in cell.bench["per_layer"]
                              if m["name"] != NAME}
    for name in CELLS:
        assert NAME in {m["name"] for m in cells.Cell(name).per_layer}
    walks = next(m for m in cell.bench["per_layer"]
                 if m["name"] == "paged_rows_per_walk.batch")
    assert walks["workloads"] == CELLS


def test_the_hosts_rule_on_the_agent_cells_tick():
    """A tile of eight decode rows on one prefix of 127 compute blocks and 7
    pages, each with 300 tokens of its own behind it: 8 x 130 blocks seen,
    127 + 8 x 3 fetched; the same rows on a table each fetch what they
    see."""
    from megatron_llm_tpu.ops.pallas.paged_attention import tile_shares

    page, pages = 16, 1112
    tables = 1 + np.arange(9 * pages).reshape(9, pages)
    tables[0] = 0
    pos = np.full(8, 16368 + 300)
    hor = (pos // 64 + 1) * 64
    kw = dict(window=None, page=page, row_bytes=8 * 2 * 128 * 2)
    alone = tile_shares(tables, 1 + np.arange(8), pos, hor, **kw)
    assert tuple(int(n) for n in alone.blocks()) == (8 * 131, 8 * 131)
    tables[2:, :1023] = tables[1, :1023]
    shared = tile_shares(tables, 1 + np.arange(8), pos, hor, **kw)
    assert tuple(int(n) for n in shared.blocks()) == (
        8 * 131, 127 + 8 * 4)
