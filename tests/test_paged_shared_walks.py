"""Rows of different sequences that name the same leading pages share ONE
page walk (PR 56), and rows of ONE table share their whole walk whatever
their positions (PR 60): the rule
(``ops/pallas/paged_attention.tile_shares``) by hand, the kernel's outputs
in interpret mode against every row walked alone, the tick's order of its
decode slots (``generation/ragged.py`` ``decode_order``) against slot
order, and the engine's counters of walks and of compute blocks.

A file of its own (the tier-1 run hands a FILE to a worker); the kernel's
calls are shared by the scenarios that stand in them (``_outputs``: one
call holds a scenario's tiles AND its rows spread one a tile), at the
smallest shapes that hold five compute blocks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.generation import ContinuousBatchingEngine, DraftModel
from megatron_llm_tpu.generation import ragged
from megatron_llm_tpu.ops.pallas import paged_attention as pk
from megatron_llm_tpu.ops.pallas.paged_attention import (
    SHARE_BLOCKS,
    SHARE_ROWS,
    SLOTS,
    TILE,
    tile_shares,
)
from tools import tpu_kernel_check as kernel_check

# every geometry's compute block is 16 pages of 8 tokens in float32 (int8
# pages: in their own bytes): a K|V pair of two heads of 128, the pair of
# 64s read whole, a latent row, int8 pages
GEOMETRIES = {
    "pair128": dict(n=4, nkv=2, d=128, page=8),
    "pair64": dict(n=4, nkv=1, d=64, page=8),
    "latent": dict(n=4, nkv=1, d=128, page=8, latent=True),
    "int8": dict(n=4, nkv=2, d=128, page=8, kv_dtype="int8"),
}
BK, END = 128, 656      # a compute block's tokens; a prefix's (5 blocks + 2 pages)

# scenario: (window, span A, span B, a row's (blk0, lo, hi, blk1)); a span
# is (rows from, to, blocks s0, s1[, the token its walk fetches up to: the
# range's end unless said]), None where its range is empty
RULE = {
    "one": (False, (0, 8, 0, 5), None, [(0, 0, 5, 6)] * 8),
    "two": (False, (0, 5, 0, 5), (5, 8, 0, 5), [(0, 0, 5, 6)] * 8),
    "dead": (False, (0, 8, 0, 5), None,
             [(0, 0, 5, 6), (0, 0, 0, 0), (0, 0, 5, 7), (0, 0, 5, 6),
              (0, 0, 0, 0), (0, 0, 5, 6), (0, 0, 0, 0), (0, 0, 0, 0)]),
    # the fourth row's last key lies in the third block: two blocks shared
    "short": (False, (0, 8, 0, 2), None,
              [(0, 0, 2, 6)] * 3 + [(0, 0, 2, 3)] + [(0, 0, 2, 6)] * 4),
    "verify": (False, (0, 8, 0, 5), None, [(0, 0, 5, 6)] * 8),
    "none": (False, None, None, [(0, 0, 0, 4)] * 8),
    # rows that share nothing, dead rows between them: walks of four, two,
    # one and three blocks, so that the first blocks of two successive walks
    # land in the SAME half of the page buffer (behind an even walk) and in
    # the other; and a tile of one block a walk
    "gaps": (False, None, None,
             [(0, 0, 0, 4), (0,) * 4, (0,) * 4, (0, 0, 0, 2), (0,) * 4,
              (0, 0, 0, 1), (0, 0, 0, 3), (0,) * 4]),
    "single": (False, None, None, [(0, 0, 0, 1)] * 8),
    # windows of four blocks: four rows' open in block 1, four in block 2,
    # whose slots behind the window name the null page: block 3 is the
    # first in which all eight agree
    "window": (True, (0, 8, 3, 5), None,
               [(1, 3, 5, 6)] * 4 + [(2, 3, 5, 7)] * 4),
    "window_two": (True, (0, 4, 2, 5), (4, 8, 2, 5), [(1, 2, 5, 6)] * 8),
    # rows of ONE table are walked whole, through the last block any of
    # them sees: a block's denoise rows behind its dead commit rows; its
    # commit tick (the commit rows' last key ends the seventh block); a
    # prompt's last tile; the commit tick under a window that opens in the
    # third block for four rows and in the fourth for the others
    "block": (False, (4, 8, 0, 8, 7 * BK + 20), None,
              [(0, 0, 0, 0)] * 4 + [(0, 0, 8, 8)] * 4),
    "commit": (False, (0, 8, 0, 8, 7 * BK + 4), None,
               [(0, 0, 7, 7)] * 4 + [(0, 0, 8, 8)] * 4),
    "tail": (False, (0, 8, 0, 3, 2 * BK + 105), None,
             [(0, 0, 3, 3)] * 5 + [(0, 0, 0, 0)] * 3),
    "window_commit": (True, (0, 8, 2, 8, 7 * BK + 2), None,
                      [(2, 2, 7, 7)] * 4 + [(3, 3, 8, 8)] * 4),
    # under the window: rows that share nothing, whose walks start at an
    # odd, an even and an odd block; and a span of five rows of which three
    # walk a block of their own in front of it and two (their windows open
    # where the span begins) have NO head, dead rows between them
    "window_gaps": (True, None, None,
                    [(1, 1, 1, 6), (0,) * 4, (2, 2, 2, 7), (0,) * 4,
                     (0,) * 4, (1, 1, 1, 5), (0,) * 4, (0,) * 4]),
    "window_heads": (True, (0, 8, 2, 5), None,
                     [(1, 2, 5, 6), (0,) * 4, (1, 2, 5, 6), (2, 2, 5, 6),
                      (0,) * 4, (1, 2, 5, 6), (2, 2, 5, 6), (0,) * 4]),
}
# the compute blocks under a scenario's masks, those its walks fetch, and
# its walks, by hand: a row that walks a block alone is a walk, a span that
# serves a row's whole walk is one
COUNTS = {
    "one": (48, 13, 8), "two": (48, 18, 8), "dead": (25, 10, 4),
    "short": (45, 31, 8), "verify": (48, 13, 8), "none": (32, 32, 8),
    "window": (40, 26, 8), "window_two": (40, 22, 8),
    "block": (32, 8, 1), "commit": (60, 8, 1), "tail": (15, 3, 1),
    "window_commit": (40, 6, 1),
    "gaps": (10, 10, 4), "single": (8, 8, 8),
    "window_gaps": (14, 14, 3), "window_heads": (23, 11, 5),
}
# the scenarios in which every walk has a first block of its own (no span of
# several tables stands in front of rows with no head): alone in a call, all
# of its walks but the first find their first block started
EVERY_WALK_ITS_OWN_START = {
    "none", "gaps", "single", "block", "commit", "tail", "window",
    "window_two", "window_commit", "window_gaps", "window_heads"}


def _walks_by_hand(tile: int, rows, spans):
    """A tile's non-empty walks in the order its program takes them, as
    ``(tile, slot, blk0, blk1)``: the rows' own heads, the spans, the rows'
    own tails."""
    heads = [(tile, t, b0, lo) for t, (b0, lo, _, _) in enumerate(rows)]
    both = [(tile, TILE + s, span[3], span[4])
            for s, span in enumerate(spans)]
    tails = [(tile, TILE + 2 + t, hi, b1)
             for t, (_, _, hi, b1) in enumerate(rows)]
    return [w for w in heads + both + tails if w[3] > w[2]]


@functools.lru_cache(maxsize=None)
def _case(geometry: str, window: bool, only=None):
    return kernel_check.share_case(
        3, window=window, dtype=jnp.float32, only=only,
        **GEOMETRIES[geometry])


@functools.lru_cache(maxsize=None)
def _traced_shares(window: bool):
    plan = _case("pair128", window)[3]
    return jax.jit(functools.partial(tile_shares, **plan[4]))(
        *(jnp.asarray(a) for a in plan[:4]))


@pytest.mark.parametrize("scenario", RULE)
def test_tile_shares_rule(scenario):
    """The second grouping rule by hand: a tile's spans, every row's own
    head and tail, and the blocks seen and fetched; the same from numpy and
    from traced arrays."""
    window, span_a, span_b, rows = RULE[scenario]
    *_, scenarios, plan = _case("pair128", window)
    at = list(scenarios).index(scenario)
    assert (SHARE_ROWS, SHARE_BLOCKS, TILE) == (3, 2, 8)
    for shares in (tile_shares(*plan[:4], **plan[4]),
                   _traced_shares(window)):
        got = np.asarray(shares.rows).reshape(-1, TILE, 4)[at]
        assert got.tolist() == [list(r) for r in rows]
        for span, want in zip(np.asarray(shares.spans)[at], (span_a, span_b)):
            table, first, end, s0, s1, fetch_end, run = span.tolist()
            assert run == 0
            if want is None:
                assert s1 == s0
            else:
                assert (first, end, s0, s1) == want[:4]
                assert fetch_end == (want[4:] or (s1 * BK,))[0]
                # the span's walk reads its first live row's table
                live = scenarios[scenario] - TILE * at
                assert table == plan[1][TILE * at + live[live >= first][0]]
        # the walks of its program: heads, spans, tails, the empty ones
        # left out
        count = int(np.asarray(shares.count)[at])
        order = np.asarray(shares.order)[at]
        assert [(at, *w) for w in order[:count, [
            pk.SLOT, pk.BLK0, pk.BLK1]].tolist()] == _walks_by_hand(
                at, rows, [(0, *s[:4]) if s else (0,) * 5
                           for s in (span_a, span_b)])
        assert not order[count:].any()
    assert isinstance(tile_shares(*plan[:4], **plan[4]).rows, np.ndarray)
    seen = sum(max(0, b1 - b0) for b0, _, _, b1 in rows)
    alone = sum(lo - b0 + b1 - hi for b0, lo, hi, b1 in rows)
    spans = sum(s[3] - s[2] for s in (span_a, span_b) if s)
    one = tile_shares(*(a[TILE * at:TILE * (at + 1)] if i else a
                        for i, a in enumerate(plan[:4])), **plan[4])
    assert tuple(int(n) for n in one.blocks()) == (seen, alone + spans)
    assert (seen, alone + spans, int(one.walks())) == COUNTS[scenario]
    # a call's first walk starts its own first block
    assert 0 <= one.carried() <= one.walks() - 1
    if scenario in EVERY_WALK_ITS_OWN_START:
        assert one.carried() == one.walks() - 1


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
def test_walk_order_names_every_walk_once_in_program_order(window):
    """The successors (``SUCC``) from the call's first walk on: every
    non-empty walk of every tile once, in the order the programs take them,
    across tiles with none (the call's last tile is dead rows); each but
    the first finds its first block started, in the half the call's blocks
    before it leave free, by the table and the last key that are its own;
    the call's last walk starts nothing."""
    tables, idx, pos, hor, kw = _case("pair128", window)[3]
    # a tile of dead rows behind the call's second tile too
    idx, pos, hor = (np.insert(a, [2 * TILE] * TILE, 0)
                     for a in (idx, pos, hor))
    shares = tile_shares(tables, idx, pos, hor, **kw)
    rows = shares.rows.reshape(-1, TILE, 4)
    want = [w for tile in range(rows.shape[0]) for w in _walks_by_hand(
        tile, rows[tile].tolist(), shares.spans[tile].tolist())]
    assert shares.count.tolist() == [
        sum(w[0] == tile for w in want) for tile in range(rows.shape[0])]
    assert shares.count[-1] == 0 and shares.count[2] == 0
    flat = shares.order.reshape(-1, pk.WALK)
    kv_end = np.where(hor > 0, np.minimum(hor, pos + 1), 0)
    at, got, blocks = int(np.flatnonzero(shares.count)[0]) * SLOTS, [], 0
    while at >= 0:
        tbl, blk0, blk1, end, slot, half, carried, succ = flat[at].tolist()
        tile = at // SLOTS
        assert at % SLOTS < shares.count[tile]
        assert (half, carried) == (blocks % 2, int(bool(got)))
        if TILE <= slot < TILE + 2:
            assert (tbl, end) == tuple(
                shares.spans[tile, slot - TILE, [0, 5]])
        else:
            row = tile * TILE + (slot if slot < TILE else slot - TILE - 2)
            assert (tbl, end) == (idx[row], kv_end[row])
        got.append((tile, slot, blk0, blk1))
        blocks += blk1 - blk0
        at = succ
    assert got == want
    # both parities of a walk's length, so both halves of a successor's
    assert {(b1 - b0) % 2 for _, _, b0, b1 in want} == {0, 1}
    assert 0 <= shares.carried() <= shares.walks() - 1


def test_tile_shares_run_is_one_span_and_few_rows_walk_alone():
    """A tile that is one run is one span, whole (no row of it walks a
    block of its own), whatever its rows share with others; two rows on one
    prefix are under ``SHARE_ROWS`` and walk alone."""
    tables = np.arange(3 * 64).reshape(3, 64)
    tables[0] = 0
    tables[2, :40] = tables[1, :40]
    run = tile_shares(tables, np.full(8, 1), 300 + np.arange(8),
                      np.full(8, 320), window=None, page=8, row_bytes=2048)
    assert run.spans[0].tolist() == [[1, 0, 8, 0, 3, 308, 1], [0] * 7]
    assert run.rows.tolist() == [[0, 0, 3, 3]] * 8
    assert run.count.tolist() == [1]
    assert run.order[0, 0].tolist() == [1, 0, 3, 308, TILE, 0, 0, -1]
    assert (int(run.walks()), int(run.carried())) == (1, 0)
    assert tuple(int(n) for n in run.blocks()) == (24, 3)
    two = tile_shares(tables, np.array([1, 2, 0, 0, 0, 0, 0, 0]),
                      np.array([400, 410, 0, 0, 0, 0, 0, 0]),
                      np.array([448, 448, 0, 0, 0, 0, 0, 0]),
                      window=None, page=8, row_bytes=2048)
    assert tuple(int(n) for n in two.blocks()) == (8, 8)
    assert two.order[0, :, pk.SLOT].tolist() == [
        TILE + 2, TILE + 3] + [0] * (SLOTS - 2)
    assert (int(two.walks()), int(two.carried())) == (2, 1)


def test_plan_walks_is_the_kernels_own_reading_or_nothing(monkeypatch):
    """``ops/paged_attention.plan_walks``: what the kernel's call would read
    off the same state, for a tick's layers to share; nothing where the
    call does not take the kernel (this CPU target)."""
    from megatron_llm_tpu.core import parallel_state
    from megatron_llm_tpu.ops import paged_attention as pa

    tables, idx, pos, hor, kw = _case("pair128", True)[3]
    state = pa.PagedState(*(jnp.asarray(a) for a in (tables, pos, hor, idx)))
    pool = jnp.zeros((2, 8, kw["page"], kw["row_bytes"] // 4), jnp.float32)
    assert pa.plan_walks(pool, state, 128, sliding_window=kw["window"]) is None
    monkeypatch.setattr(parallel_state, "target_platform", lambda: "tpu")
    plan = pa.plan_walks(pool, state, 128, sliding_window=kw["window"])
    assert pa.plan_walks(pool, state._replace(table_index=None), 128) is None
    for got, want in zip(plan, tile_shares(tables, idx, pos, hor, **kw)):
        assert np.array_equal(np.asarray(got), want)


@functools.lru_cache(maxsize=None)
def _outputs(geometry: str, window: bool, only=None):
    """The kernel's outputs of one call (interpret mode): the scenarios'
    tiles, and behind them every row the first of a tile of its own with
    dead rows behind it, which is the one-row walk; and the gather
    path's."""
    pallas_fn, jnp_fn, scenarios, _ = _case(geometry, window, only)
    out, alone = pallas_fn(True, "beside")
    return out, alone, jnp_fn(), scenarios


def _check(geometry, scenario, only=None):
    out, alone, ref, scenarios = _outputs(
        geometry, RULE[scenario][0], only)
    rows = scenarios[scenario]
    # the same keys in the same blocks in the same order, the same
    # arithmetic a row: the bits of a walk of its own
    assert np.array_equal(np.asarray(out[rows]), np.asarray(alone[rows]))
    assert kernel_check.max_err(out[rows], ref[rows]) < 1e-5
    dead = np.setdiff1d(np.arange(out.shape[0]),
                        np.concatenate(list(scenarios.values())))
    assert not np.asarray(out[dead]).any(), "a dead row writes zeros"


@pytest.mark.parametrize("scenario", RULE)
def test_shared_walk_is_the_one_row_walk(scenario):
    """Every row of every scenario, under the full mask and under a window
    with slid tables: bit-equal to that row walked alone, and the gather
    path's."""
    _check("pair128", scenario)


@pytest.mark.parametrize("geometry,scenario", [
    ("pair64", "two"), ("latent", "window_two"), ("int8", "two"),
    ("pair64", "commit"), ("latent", "window_commit"), ("int8", "block"),
    ("pair64", "single"), ("latent", "window_heads"), ("int8", "gaps"),
    ("latent", "gaps"), ("int8", "window_gaps")])
def test_shared_walk_is_the_one_row_walk_at_every_row_kind(
        geometry, scenario):
    """Two spans in one tile, one table's rows at two positions, and walks
    that start one another's first block across dead rows, empty heads and
    a dead tile, on the pair of 64s read whole, on a latent row (under a
    window) and on int8 pages with their scales."""
    _check(geometry, scenario, only=(scenario,))


# ---------------------------------------------------------------------------
# The tick's order
# ---------------------------------------------------------------------------

VOCAB = 67


@pytest.fixture(scope="module")
def models():
    from megatron_llm_tpu.models import init_model_params, make_config

    def mk(layers, hidden, heads, nkv, ffn):
        return make_config(
            "llama2", num_layers=layers, hidden_size=hidden,
            num_attention_heads=heads, num_attention_heads_kv=nkv,
            ffn_hidden_size=ffn, seq_length=1024,
            max_position_embeddings=1024, vocab_size=VOCAB,
            hidden_dropout=0.0, attention_dropout=0.0,
            params_dtype="float32", use_flash_attn=False,
        )

    cfg = mk(2, 64, 4, 2, 128)
    dcfg = mk(1, 32, 2, 2, 64)
    return {"cfg": cfg,
            "params": init_model_params(cfg, jax.random.PRNGKey(0)),
            "draft": DraftModel(
                dcfg, init_model_params(dcfg, jax.random.PRNGKey(1)))}


def test_decode_order_lays_one_prefix_side_by_side():
    """Slots by the first page of the table that keeps every key: equal
    first pages side by side in slot order, dead slots (the null table)
    last; numpy and traced agree."""
    first = np.array([7, 0, 3, 7, 0, 3, 9, 7], np.int32)
    want = [2, 5, 0, 3, 7, 6, 1, 4]
    tables = np.stack([first, np.arange(8)], axis=1)
    assert ragged.decode_order(tables).tolist() == want
    assert np.asarray(
        ragged.decode_order(jnp.asarray(tables))).tolist() == want


def _slot_state(rng, slots, pages, page):
    """Eight slots' tables on two prefixes in no order, two of them dead,
    every live slot's own page behind its prefix."""
    prefix = {"A": rng.permutation(20)[:2] + 1, "B": 30 + rng.permutation(20)[:2]}
    tables = np.zeros((slots, pages), np.int32)
    positions = np.zeros((slots,), np.int32)
    for slot, name in enumerate("ABdABBdA"):
        if name != "d":
            tables[slot, :2] = prefix[name]
            tables[slot, 2] = 60 + slot
            positions[slot] = 2 * page + 1 + slot
    return tables, positions


@pytest.mark.parametrize("spec_k", [0, 2], ids=["tick", "spec_tick"])
def test_tick_in_decode_order_is_the_tick_in_slot_order(
        models, spec_k, monkeypatch):
    """``tick`` and ``spec_tick`` on slots whose prefixes stand in no
    order, dead slots among them: tokens, log-probs and the pool as with
    the slots run in slot order, bit for bit."""
    from megatron_llm_tpu.generation.pools import PagedKVPool

    cfg, draft = models["cfg"], models["draft"]
    slots, pages, page = 8, 4, 16
    rng = np.random.default_rng(5)
    tables, positions = _slot_state(rng, slots, pages, page)
    pool = PagedKVPool(cfg, 80, page,
                       draft_cfg=draft.cfg if spec_k else None)

    def noise(leaf, seed):
        return jax.random.normal(jax.random.PRNGKey(seed), leaf.shape,
                                 leaf.dtype)

    state = dict(
        tokens=jnp.asarray(rng.integers(2, VOCAB, slots), jnp.int32),
        keys=jnp.asarray(rng.integers(0, 2 ** 31, (slots, 2)), jnp.uint32),
        steps=jnp.arange(slots, dtype=jnp.int32),
        temp=jnp.asarray([1.0, 0.8] * 4, jnp.float32),
        top_k=jnp.asarray([1, 5] * 4, jnp.int32),
        top_p=jnp.zeros((slots,), jnp.float32))

    def run():
        fn = jax.jit(ragged.make_ragged_tick_fn(
            cfg, draft.cfg if spec_k else None, spec_k, 0))
        head = (models["params"],) + (
            (draft.params, noise(pool.kv, 1), noise(pool.draft_kv, 2))
            if spec_k else (noise(pool.kv, 1),))
        tail = ((jnp.full((slots,), spec_k, jnp.int32),) if spec_k else
                (state["tokens"], jnp.zeros((slots,), bool)))
        return fn(*head, jnp.asarray(tables), jnp.asarray(positions),
                  state["tokens"], state["keys"], state["steps"],
                  state["temp"], state["top_k"], state["top_p"], *tail)

    ordered = run()
    assert ragged.decode_order(tables).tolist() != list(range(slots))
    monkeypatch.setattr(ragged, "decode_order",
                        lambda tables: jnp.arange(tables.shape[0]))
    in_slot_order = run()
    for got, want in zip(jax.tree.leaves(ordered),
                         jax.tree.leaves(in_slot_order)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# The engine's counters
# ---------------------------------------------------------------------------


def _counters():
    from megatron_llm_tpu.observability import registry as registry_mod

    reg = registry_mod.get_registry()
    return np.array([reg.counter(f"mlt_engine_paged_{name}_total").value
                     for name in ("blocks_seen", "blocks_fetched", "rows",
                                  "walks", "carried_walks")])


def test_engine_counts_blocks_by_the_kernels_rule(models, monkeypatch):
    """``mlt_engine_paged_blocks_seen_total`` / ``_fetched_total`` rise by
    what ``tile_shares`` gives for each launched tick's plan times the
    layers, ``_rows_total`` / ``_walks_total`` by its live rows and its
    ``walks()``, ``_carried_walks_total`` by its ``carried()``; requests on
    one primed prefix of two compute blocks and more fetch fewer blocks
    than their rows see, tick after tick."""
    from megatron_llm_tpu.generation import engine as engine_mod

    cfg, params = models["cfg"], models["params"]
    layers = cfg.model.num_layers
    eng = ContinuousBatchingEngine(cfg, params, None, max_slots=8,
                                   max_seq=768)
    given = []

    def spy(*args, **kw):
        shares = tile_shares(*args, **kw)
        given.append([layers * int(n) for n in shares.blocks()]
                     + [int((args[3] > 0).sum()), int(shares.walks()),
                        int(shares.carried())])
        return shares

    monkeypatch.setattr(engine_mod, "tile_shares", spy)
    # a compute block of this pool is 256 tokens (a row of 256 bytes)
    prefix = [2 + (i * 7) % 60 for i in range(560)]
    kw = dict(top_k=1, termination_id=10 ** 9)
    eng.submit(prefix + [5], 2, **kw)                           # primes
    eng.run_until_idle()
    before, ticks = _counters(), len(given)
    reqs = [eng.submit(prefix + [9 + i] * (3 + i), 6, **kw)
            for i in range(4)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=5)
    seen, fetched, rows, walks, carried = _counters() - before
    assert [seen, fetched, rows, walks, carried] == np.sum(
        given[ticks:], axis=0).tolist()
    # a prompt's rows of one table are walked together, a decode row alone
    assert 0 < walks < rows
    # every tick's first walk starts its own first block
    assert 0 < carried <= walks - (len(given) - ticks)
    # four decode rows on one prefix: two blocks walked once, not four
    # times, in every tick that held three of them or more
    decode = [g for g in given[ticks:] if g[0] - g[1] >= 2 * layers * 2]
    assert len(decode) >= 4 and fetched < seen


def test_engine_unshared_plan_counts_the_runs_saving_alone(models):
    """Requests that share nothing, contexts under one compute block: every
    row sees one block and fetches it, but a run's eight rows fetch one:
    seen less fetched is rows less walks, times the layers."""
    cfg, params = models["cfg"], models["params"]
    eng = ContinuousBatchingEngine(cfg, params, None, max_slots=8,
                                   max_seq=256, prefix_cache=False)
    before = _counters()
    reqs = [eng.submit([3 + (i * 5 + j) % 60 for j in range(40 + 9 * i)], 6,
                       top_k=1, termination_id=10 ** 9) for i in range(5)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=5)
    seen, fetched, rows, walks, carried = _counters() - before
    assert rows > walks > carried > 0
    assert seen == cfg.model.num_layers * rows
    assert seen - fetched == cfg.model.num_layers * (rows - walks)
