"""Pallas TPU paged-attention kernel (Ragged Paged Attention style).

One kernel, one call shape: a RAGGED batch of single-token rows (decode,
verify and prefill rows, each with its own table index, position and kv
horizon).  The engine's tick is that batch; a DECODE step (one row per
sequence) and a PREFILL CHUNK (s rows of one sequence at consecutive
positions) are ragged batches too, and are launched as such.  The [b,
max_pages*page_size] gather of the jnp path (ops/paged_attention.py) never
materializes.

The grid runs over TILES of ``TILE`` (8) consecutive rows; the pools stay
in HBM and the block tables, table indices, positions and horizons are
*scalar-prefetch* operands in SMEM.  A PAGE WALK is a loop over a context
in COMPUTE BLOCKS of several pages (_pages_per_step: 128 tokens or more),
from the first block the sliding window still sees to the one that holds
the last visible key: the trip count is data, so table slots past a
context are never looked up, a dead row (horizon 0) runs zero trips and
writes zeros, and the cost of a call does not depend on the table's width
(``engine_max_seq``).  Each step starts the copies of the NEXT block's
pages (``page_id = tables[table_index[row], j]``, one copy a page, all kv
heads of the page at once) into the other half of a double-buffered VMEM
scratch, waits for the current half, and does one score matmul and one
online-softmax update per kv head for the whole block.  Behind a walk's
LAST block the next block is the FIRST block of the walk the call takes
next (:func:`walk_order`: the next walk of the tile's program, behind its
last one the first walk of the next program that has any), started under
that walk's table and last key: a walk finds its first block on the way
and only a call's first walk starts a copy and waits for it with nothing
to compute meanwhile.  The last block of a walk is partial: pages past
the context are not fetched, their columns are masked and their value rows
zeroed.  The softmax state (running max m, normalizer l, fp32 accumulator)
of the tile's rows lives in VMEM scratch — the blockwise scheme of
ops/pallas/flash_attention.py with blocks of pages as KV blocks.  GQA is
native, with no K/V expansion and no copy of the query: a program's query
and output blocks are its tile's rows ROW-MAJOR, as the attention layer
holds them (``[TILE, heads, d]``), and a kv head's query rows are a slice
of the block inside the kernel (``_paged_kernel``).

WHO SHARES A WALK is read from the call's own data, by ONE rule the engine
counts by too (:func:`tile_shares`; a tile holds up to two SPANS, stretches
of consecutive rows that agree on their pages).  A span whose live rows all
name ONE TABLE — a prompt chunk's rows, a verify block, a prompt's last
rows with dead ones behind, a diffusion block's denoise rows and the commit
rows of the block before (generation/blocks.py) — names the same pages
everywhere, whatever the rows' positions, and is walked ONCE and WHOLE:
from the first block any of its rows sees through the last one any of them
sees, with one score matmul and one value matmul a kv head for all ``TILE *
group`` query rows, the causal (and window) mask and the softmax state per
row.  A block outside one row's mask leaves that row's state as it was
(alpha 1, p 0), so each row's result is what a walk of its own gives.  A
RUN (:func:`tile_runs`: a tile's eight rows all live, on one table, at
consecutive positions) is the special case whose masks come from an iota
and not from a look at each row.  Rows of DIFFERENT sequences whose tables
name the same pages over a range of compute blocks — sequences on one
cached prefix, which the tick lays side by side
(generation/ragged.decode_order) — are served that range by ONE walk too,
the tile's rows in the matmul and the others masked, but only the whole
blocks below every row's last key (behind a prefix each sequence names
pages of its own), and each row walks what is its own before it (a
window's first blocks) and behind it (its own pages) alone, ``group``
query rows a matmul: every row meets the blocks it met alone, in the same
order, with the same arithmetic a row.  Rows that agree with nobody —
decode rows on a table each — walk one after another inside the program: a
row alone costs what it cost when the grid ran over rows.  All of it is
data (``walk_ref`` / ``count_ref`` / ``span_ref``: a tile's non-empty walks
in the order its program takes them — each row's own head, each span's
range, each row's own tail — and which rows a span holds), so a tick's
composition never recompiles, and the kernel holds ONE loop over a tile's
walks with ONE traced one-row walk and ONE traced tile walk in it,
whatever a tile's program is.

Layout rules (Mosaic).  A copy out of HBM moves whole 128-lane rows, and
the pool is STORED in such rows (ops/kv_quant.py owns the row): ``[pages,
page, H*d]``, for a K/V pool a head's key and value side by side
(``H = 2*nkv``), every layer's pages in one flat array.  The kernel reads
it as it lies — no pad, no slice of a layer in front of the call: ONE copy
a page brings keys and values, ``page_base`` (a scalar-prefetch operand)
is the calling layer's first page.  In VMEM a head's lanes are sliced out
of the copy in whole 128-lane groups: with ``d % 128 == 0`` the key's
``d`` lanes and the value's next to them; with ``d = 64`` the head's
128-lane PAIR is read as key and as value at once — the query is zero on
the value's lanes, the output's value lanes are kept — which costs a
128-wide MXU nothing (``_head_lanes``; ops/paged_attention._kernel_refusal
is the whole rule).  A latent pool (``latent``) is one head whose row is
key and value.  Per-page scales ``[P, H]`` (the calling layer's) are read
as 128-lane rows of their flat view into SMEM, one copy per fetched page.

Numerics match the jnp path: fp32 logits/softmax/accumulator, outputs cast
to the query dtype.  The OPERANDS of the two matmuls follow what the call
observes (``_operand_dtype``; no flag): a bf16 query on pages whose values
bf16 holds exactly (bf16, int8, fp8) multiplies bf16 by bf16 — a product of
two bf16 values is exact in float32 and the MXU accumulates in float32, so
the scores are what float32 operands give, in ONE pass over a key tile that
is read as it lies in the page buffer; ``scale`` multiplies the float32
scores.  The probabilities stay float32 in VALUE: ``p = p_hi + p_lo``, each
half a bf16 (``_weighted_values``; 16 bits of mantissa, relative error
2^-17 where a plain cast to bf16 has 2^-9), two MXU passes of the rows over
one value tile.  A float32 query, or a float32 pool, keeps float32
operands: rounding either would change the numbers, and that path is the
tests' exactness oracle (in interpret mode; on the chip Mosaic's float32
matmul at default precision rounds its operands to bf16 inside the MXU,
so there the bf16 path is the more exact of the two).  Quantized pools
(ops/kv_quant.QuantPagedKV) arrive in their storage dtype; the cast to the
operand dtype and the scale multiply (on the scores' and probabilities'
columns: ``q . (k*s) == (q . k)*s``) happen inside the step that consumes
the page, so HBM traffic is the quantized bytes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops import kv_quant

NEG_INF = -1e30


# Query rows a program: the sublane count of a 32-bit tile, so that a row's
# ``group`` query heads (padded to whole tiles) start on one.  It divides
# the engine's slot counts and its prompt-row buckets, so a tick's prompt
# rows fill whole tiles and none holds decode and prompt rows at once.
TILE = 8


def tile_runs(table_index, positions, horizons):
    """Which tiles of ``TILE`` consecutive rows are RUNS, a pure function
    of the call's data: the special case of :func:`tile_shares`' span of
    one table whose rows' masks the kernel writes from an iota.

    ``[R]`` arrays (numpy, on the host; traced, in front of the kernel) ->
    ``(shared, live)``, both ``[ceil(R / TILE)]``: ``shared[i]`` says that
    tile ``i`` is ONE RUN — every row live (its horizon past its position),
    all on one table, at consecutive positions — and ``live[i]`` counts its
    rows with a horizon.  What a tile's walks cost is the whole rule's to
    say (``TileShares.walks``).  Rows past the last whole tile count as
    dead ones (the wrapper pads with them)."""
    xp = jnp if any(isinstance(a, jax.Array)
                    for a in (table_index, positions, horizons)) else np
    pad = (0, -table_index.shape[0] % TILE)
    idx, pos, hor = (xp.pad(a, pad).reshape(-1, TILE)
                     for a in (table_index, positions, horizons))
    run = ((hor > pos) & (idx == idx[:, :1])
           & (pos - pos[:, :1] == np.arange(TILE)))
    return run.all(axis=1), (hor > 0).sum(axis=1)


# From how many live rows, and over how many compute blocks, ONE walk with
# a tile's ``TILE * group`` query rows in the matmul beats the rows' own
# walks (``group`` rows a matmul each); timed on a v5e (PERF.md section 6,
# PR 56), as ``STACK_ROWS`` was
SHARE_ROWS = 3
SHARE_BLOCKS = 2


# A tile's page walks in the order its program takes them: each row's own
# head, the two spans, each row's own tail (a SLOT each; the empty ones are
# left out of the list the kernel loops over)
SLOTS = 2 * TILE + 2
# a walk's record in ``TileShares.order``: the table it reads, its compute
# blocks [blk0, blk1), the token it fetches up to, its slot, the half of the
# page buffer its first block lands in, whether a walk before it starts that
# block's copies, and the walk whose first block IT starts (that walk's
# place in the call's list, -1: none)
WALK = 8
TBL, BLK0, BLK1, KV_END, SLOT, HALF, CARRIED, SUCC = range(WALK)


class TileShares(NamedTuple):
    """What :func:`tile_shares` reads off a call: every row's walk in
    compute blocks (``blk0`` .. ``blk1``, its mask's first and last), the
    part of it a span's shared walk serves (``lo`` .. ``hi``; empty, at
    ``blk0``, for a row that walks alone; the whole of it for a row of a
    span of one table), a tile's two spans, and the tile's non-empty walks
    as the kernel takes them, one after another (:func:`walk_order`)."""

    rows: object     # [R', 4] int32: blk0, lo, hi, blk1 (R' in whole tiles)
    spans: object    # [tiles, 2, 7]: table, rows from, to, blocks s0, s1,
    #                  the token its walk fetches up to, whether it is a run
    #                  (all zeros: a span with no range)
    order: object    # [tiles, SLOTS, WALK]: a tile's walks, the first
    #                  ``count`` of its records
    count: object    # [tiles]: the walks of a tile's program

    def blocks(self):
        """``(seen, fetched)``: the compute blocks under the live rows'
        masks, summed over rows, and the blocks the walks fetch: each
        row's own head and tail and each span's range once."""
        blk0, lo, hi, blk1 = (self.rows[:, k] for k in range(4))
        own = (lo - blk0).clip(0) + (blk1 - hi).clip(0)
        shared = (self.spans[..., 4] - self.spans[..., 3]).clip(0)
        return (blk1 - blk0).clip(0).sum(), own.sum() + shared.sum()

    def _walkers(self):
        """``(alone, whole, inside)``: the rows that walk a head or a tail
        of their own ``[tiles, TILE]``, and by span ``[tiles, 2, TILE]``
        the rows whose WHOLE walk the span serves and the rows it holds."""
        blk0, lo, hi, blk1 = (
            self.rows.reshape(-1, TILE, 4)[..., k] for k in range(4))
        alone = (lo > blk0) | (blk1 > hi)
        first, end = (self.spans[..., k, None] for k in (1, 2))
        r = np.arange(TILE)
        inside = (r >= first) & (r < end)
        return alone, ((blk1 > blk0) & ~alone)[:, None] & inside, inside

    def walks(self):
        """The page walks the call costs: one a row that walks a head or a
        tail of its own, one a span that serves a row's WHOLE walk (a run,
        a span of one table; a span whose rows each walk a tail besides
        costs no walk more than they do)."""
        alone, whole, _ = self._walkers()
        return alone.sum() + whole.any(axis=2).sum()

    def carried(self):
        """Of those walks, the ones whose FIRST block's copies a walk
        before them starts (``CARRIED`` of the record that fetches it: a
        row's head, else the span that holds it where that has a range,
        else its tail; a span's own).  A call's first walk starts its own,
        so this is at most ``walks()`` less one a call with a walk."""
        alone, whole, inside = self._walkers()
        blk0, lo, hi, _ = (
            self.rows.reshape(-1, TILE, 4)[..., k] for k in range(4))
        # the records' flags back in their slots
        flag = np.zeros(self.order.shape[:2], bool)
        tile, at = np.nonzero(np.arange(SLOTS) < self.count[:, None])
        flag[tile, self.order[tile, at, SLOT]] = (
            self.order[tile, at, CARRIED] != 0)
        r = np.arange(TILE)
        opens = np.where(lo > blk0, r, np.where(
            hi > lo, TILE + inside[:, 1], TILE + 2 + r))
        return ((alone & np.take_along_axis(flag, opens, axis=1)).sum()
                + (whole.any(axis=2) & flag[:, TILE:TILE + 2]).sum())


def walk_order(rows, spans, table_index, kv_end):
    """A call's page walks as records (``WALK``), tile by tile in the order
    the kernel's program takes them, the empty ones left out: ``(order
    [tiles, SLOTS, WALK], count [tiles])`` of the rule's ``rows`` and
    ``spans`` and each row's table and last key + 1 (``[R']``, whole
    tiles).

    The list is what lets a walk START ITS SUCCESSOR'S FIRST BLOCK: a
    walk's last step has no next block of its own to fetch while it
    computes, so it starts the copies of the first block of the walk the
    program takes next — the next record of its tile, behind a tile's last
    the first of the next tile that has any (the grid is one sequential
    dimension and the page buffer outlives a program) — and only a call's
    first walk starts its own and waits with nothing to do.  A block lands
    in the half of the page buffer the block before it, of whichever walk,
    is not in: ``HALF`` counts the call's blocks, not a walk's."""
    xp = jnp if any(isinstance(a, jax.Array) for a in (
        rows, spans, table_index, kv_end)) else np
    blk0, lo, hi, blk1 = (rows.reshape(-1, TILE, 4)[..., k] for k in range(4))
    idx, kv_end = (a.reshape(-1, TILE) for a in (table_index, kv_end))
    tiles = idx.shape[0]

    def slots(heads, of_spans, tails):
        return xp.concatenate([heads, of_spans, tails], axis=1)

    first = slots(blk0, spans[..., 3], hi)
    last = slots(lo, spans[..., 4], blk1)
    steps = (last - first).clip(0)
    live = steps > 0
    # the call's blocks before a walk's first: its parity is the half
    done = xp.cumsum(steps.reshape(-1)).reshape(tiles, SLOTS) - steps
    at = xp.cumsum(live, axis=1)
    count = at[:, -1]
    # a tile's j-th walk lies in the first slot with j + 1 walks up to it
    k = np.arange(SLOTS)
    src = xp.minimum((at[:, None] <= k[:, None]).sum(axis=2), SLOTS - 1)
    # the next tile with a walk (``tiles``: none)
    tile = np.arange(tiles)
    ahead = xp.where((count > 0) & (tile > tile[:, None]), tile, tiles).min(
        axis=1)
    succ = xp.where(k + 1 < count[:, None], tile[:, None] * SLOTS + k + 1,
                    xp.where(ahead < tiles, ahead * SLOTS, -1)[:, None])
    before = (xp.cumsum(count) - count)[:, None] + k
    by_slot = xp.stack([
        slots(idx, spans[..., 0], idx), first, last,
        slots(kv_end, spans[..., 5], kv_end),
        xp.broadcast_to(k, live.shape), done % 2], axis=2)
    order = xp.concatenate([
        xp.take_along_axis(by_slot, src[..., None], axis=1),
        xp.stack([before > 0, succ], axis=2)], axis=2)
    # behind a tile's walks: zeros
    order = xp.where((k < count[:, None])[..., None], order, 0)
    return order.astype(np.int32), count.astype(np.int32)


def tile_shares(tables, table_index, positions, horizons, *,
                window: Optional[int], page: int,
                row_bytes: int) -> TileShares:
    """THE grouping rule, a pure function of the call's data (numpy on the
    host, traced in front of the kernel): which COMPUTE BLOCKS (``pps``
    pages of ``page`` tokens: ``_pages_per_step`` of a token's
    ``row_bytes``) of a tile's rows one page walk serves for several rows
    at once.

    A SPAN is a stretch of a tile's consecutive rows whose live ones name
    the same pages.  A tile has up to two: the rows that agree with its
    first live row, then those that agree with the first that does not
    (two prefixes meet in the tile), told apart in the block after the
    last first-visible block among the tile's rows, which every span worth
    a walk holds.

    A span whose live rows all carry ONE ``table_index`` (a prompt's rows,
    a verify block, a diffusion block's denoise and commit rows) names the
    same pages everywhere: its range ``[s0, s1)`` runs from the FIRST
    first-visible block among its rows through the LAST block any of them
    sees, its walk fetches keys up to the largest of their last keys, and
    no row of it walks a block alone; each row's mask (its position, its
    window, its last key) is the kernel's to apply.  A tile that is one run
    (:func:`tile_runs`) is such a span, however short.

    A span of SEVERAL tables — rows of different sequences on one cached
    prefix, which the tick lays side by side
    (generation/ragged.decode_order) — shares the first stretch of blocks,
    from the last first-visible block among its rows on, in which every
    live row names the anchor's ``pps`` pages and which lie wholly below
    every live row's last key (a window class's table names the null page
    behind a row's window, so rows agree only from the latest start on;
    whole blocks only: behind a prefix each sequence names pages of its
    own); every row still meets the blocks it met alone, in ascending
    order: its own head ``[blk0, s0)``, the shared ``[s0, s1)``, its own
    tail ``[s1, blk1)``.

    Either range is empty where one walk with the tile's rows would not
    beat the rows' own: under ``SHARE_ROWS`` live rows or ``SHARE_BLOCKS``
    blocks (a run apart)."""
    xp = jnp if any(isinstance(a, jax.Array) for a in (
        tables, table_index, positions, horizons)) else np

    def padded(a, axis, size):      # zeros behind, where any are wanted
        lack = [(0, size - a.shape[axis] if k == axis else 0)
                for k in range(a.ndim)]
        return xp.pad(a, lack) if lack[axis][1] else a

    pps = _pages_per_step(page, row_bytes)
    bk = pps * page
    rows = -(-table_index.shape[0] // TILE) * TILE
    idx, pos, hor = (padded(a, 0, rows).reshape(-1, TILE).astype(np.int32)
                     for a in (table_index, positions, horizons))
    live = hor > 0
    kv_end = xp.where(live, xp.minimum(hor, pos + 1), 0)
    blk0 = (xp.zeros_like(pos) if window is None
            else xp.maximum(pos - window + 1, 0) // bk)
    blk1 = (kv_end + bk - 1) // bk
    run, _ = tile_runs(idx.reshape(-1), pos.reshape(-1), hor.reshape(-1))
    width = tables.shape[1]
    nblk = -(-width // pps)
    named = tables[idx]                        # [tiles, TILE, width]
    r = np.arange(TILE)
    blk = np.arange(nblk)

    tile = np.arange(idx.shape[0])
    # who stands with whom, read in ONE block every span worth a walk holds
    told = xp.minimum(xp.where(live, blk0, 0).max(axis=1) + 1, nblk - 1)
    pages = named[tile[:, None, None], r[:, None], xp.minimum(
        told[:, None] * pps + np.arange(pps), width - 1)[:, None]]

    def stretch(first):
        """Rows ``[first, end)``: up to the first live row behind ``first``
        that names other pages than row ``first`` (TILE: none does)."""
        other = (pages != pages[tile, xp.minimum(first, TILE - 1)][
            :, None]).any(axis=2)
        return xp.where(live & other & (r > first[:, None]), r,
                        TILE).min(axis=1)

    first_a = xp.argmax(live, axis=1)
    first_b = stretch(first_a)
    # the two spans side by side, [tiles, 2]: rows [first, end), the row
    # whose table the walk reads
    first = xp.stack([first_a, first_b], axis=1)
    end = xp.stack([first_b, stretch(first_b)], axis=1)
    anchor = xp.minimum(first, TILE - 1)
    mine = live[:, None] & (r >= first[..., None]) & (r < end[..., None])
    table = idx[tile[:, None], anchor]
    # a span of ONE table (a run is one): its rows name the same pages
    # everywhere, whatever their positions
    one = ~(mine & (idx[:, None] != table[..., None])).any(axis=2)

    def over(a, fill, least: bool):     # of a span's live rows, [tiles, 2]
        a = xp.where(mine, a[:, None], fill)
        return a.min(axis=2) if least else a.max(axis=2)

    # where a row names another page than its span's anchor
    differ = named != named[tile[:, None], xp.where(
        r < first_b[:, None], anchor[:, :1], anchor[:, 1:])]
    # a block is a span's where no live row of it differs in a page (slots
    # past the table's width lie past every context: never below a last
    # key) and it lies below ``upto``: of one table the last block any row
    # sees, of several the block the first last key lies in
    ok = ~padded((differ[:, None] & mine[..., None]).any(axis=2), 2,
                 nblk * pps).reshape(-1, 2, nblk, pps).any(axis=3)
    upto = xp.where(one, over(blk1, 0, False),
                    over(kv_end, np.iinfo(np.int32).max, True) // bk)
    ok &= blk < upto[..., None]
    start = xp.where(one, over(blk0, nblk, True), over(blk0, 0, False))
    s0 = xp.where(ok & (blk >= start[..., None]), blk, nblk).min(axis=2)
    s1 = xp.where(~ok & (blk >= s0[..., None]), blk, nblk).min(axis=2)
    # a run is walked whole however short it is
    pays = (mine.sum(axis=2) >= SHARE_ROWS) & (
        (s1 - s0 >= SHARE_BLOCKS) | run[:, None])
    shared = mine & pays[..., None]
    # a row's part of its span's range, inside its own walk; none: empty,
    # where its walk starts
    lo = xp.where(shared[:, 0], s0[:, :1],
                  xp.where(shared[:, 1], s0[:, 1:], blk0))
    hi = xp.where(shared[:, 0], s1[:, :1],
                  xp.where(shared[:, 1], s1[:, 1:], blk0))
    lo = xp.minimum(xp.maximum(lo, blk0), blk1)
    hi = xp.minimum(xp.maximum(hi, lo), blk1)
    # a span with no range is all zeros
    spans = xp.where(pays[..., None], xp.stack(
        [table, first, end, s0, s1,
         xp.minimum(s1 * bk, over(kv_end, 0, False)),
         run[:, None] & pays], axis=2), 0).astype(np.int32)
    rows = xp.stack([blk0, lo, hi, blk1], axis=2).reshape(-1, 4).astype(
        np.int32)
    return TileShares(rows, spans, *walk_order(
        rows, spans, idx.reshape(-1), kv_end.reshape(-1)))


def _operand_dtype(q_dtype, page_dtype, quantized: bool):
    """The dtype both matmuls take their operands in, read off the call: a
    bf16 query on pages whose values bf16 holds exactly (bf16 pages; an
    int8 or fp8 page, 8 bits of mantissa or fewer) multiplies bf16 by bf16,
    which rounds nothing — the products are exact in float32 and are summed
    in float32.  Any other pairing (a float32 query, a float32 pool) keeps
    float32 operands: rounding one of them would change the result."""
    exact = quantized or page_dtype == jnp.bfloat16
    return jnp.dtype(jnp.bfloat16 if q_dtype == jnp.bfloat16 and exact
                     else jnp.float32)


# Rows of one value matmul from which the probabilities' two halves go
# through the MXU stacked; measured on a v5e (PERF.md section 6, PR 45)
STACK_ROWS = 64


def _weighted_values(p, v):
    """``p @ v`` for float32 probabilities ``p [rows, bk]`` and a value tile
    ``v [bk, w]`` in the operands' dtype, accumulated in float32.  Float32
    values: the product as it is.  bf16 values: ``p`` is NOT rounded to
    bf16 — it is split into two bf16 halves whose sum carries 16 bits of
    its mantissa (``p_hi`` the nearest bf16, ``p_lo`` the nearest bf16 of
    what that left; ``p - p_hi`` is exact in float32), and ``p_hi @ v +
    p_lo @ v`` differs from the float32 product by 2^-17 of it, under the
    float32 sum's own order effects at these lengths.  Two MXU passes of
    the rows over one value tile: a row's group alone (8-32 rows) as two
    matmuls with the same right-hand side, a run's ``TILE * group`` rows
    or a wide group (``STACK_ROWS`` and more) with the halves stacked along
    the rows of one — which of the two forms Mosaic schedules better was
    measured, not reasoned."""
    dims = (((1,), (0,)), ((), ()))

    def dot(a):
        return jax.lax.dot_general(
            a, v, dims, preferred_element_type=jnp.float32)

    if v.dtype == jnp.float32:
        return dot(p)
    rows = p.shape[0]
    p_hi = p.astype(v.dtype)
    hi32 = p_hi.astype(jnp.float32)
    p_lo = p - hi32
    if rows < STACK_ROWS:
        return dot(p_hi) + dot(p_lo.astype(v.dtype))
    # stacked in float32, where 8 rows are a whole sublane tile
    both = dot(jnp.concatenate([hi32, p_lo], axis=0).astype(v.dtype))
    return both[:rows] + both[rows:]


def _paged_kernel(
    # scalar prefetch — all traced data, so one compiled launch serves any
    # tick composition
    tbl_ref,     # [T, max_pages] int32 block tables
    pos_ref,     # [b] int32 the row's position
    hor_ref,     # [b] int32 kv horizon in tokens (0 = dead row)
    span_ref,    # [b / TILE * 14] int32: a tile's two spans (tile_shares)
    walk_ref,    # [b / TILE * SLOTS * WALK] int32: the call's walks in the
    #              order the programs take them (walk_order)
    count_ref,   # [b / TILE] int32: the walks of a tile's program
    base_ref,    # [1] int32 first page of the calling layer in the pool
    # q block, the pool in HBM [, its scales], out block, then scratch
    *refs,
    group: int,
    scale: float,
    operand,
    sliding_window: Optional[int],
    quantized: bool,
    paired: bool,
):
    """One program per TILE of rows.  The query and output blocks are the
    tile's rows as the layer holds them, ``[TILE, nkv * group, w]``: a
    row's heads one kv head after another, ``group`` query rows a kv head
    and row (the heads of its group, padded to whole sublane tiles).  The
    kv-head-major order the matmuls want is an INDEX here, not a copy
    around the call: a row's own walk reads ``q_ref[t, heads(h)]``, a
    span's walk ``q_ref[:, heads(h)]`` folded to ``[TILE * group, w]``,
    which in float32 with ``group`` in whole tiles of 8 moves no data; the
    softmax state stays ``[nkv, TILE * group, .]`` and the last lines write
    ``acc / l`` back head by head.  The query is UNSCALED and in float32 —
    an exact copy of a bf16 query, whose rows are sliced where a 32-bit
    tile starts and cast to ``operand``, the dtype both matmuls take their
    operands in (``_operand_dtype``), beside the matmul.
    ``paired``: a head's ``w`` key lanes are followed by its ``w`` value
    lanes; otherwise its ``w`` lanes are key and value at once (a latent
    row; a K|V pair of 64s)."""
    if quantized:
        (q_ref, kv_hbm, s_hbm, o_ref,
         kv_buf, sem, m_s, l_s, acc_s, s_buf) = refs
    else:
        q_ref, kv_hbm, o_ref, kv_buf, sem, m_s, l_s, acc_s = refs
    i = pl.program_id(0)
    nkv, _, w = acc_s.shape
    _, pps, page, _ = kv_buf.shape
    bk = pps * page
    # storage heads a page's scale row holds: a key and a value per head
    n_scales = 2 * nkv

    def page_scales(slot, at, h, live):
        """[1, bk] row of storage head ``h``'s per-page scales, 0 where not
        live; ``at[j]`` is where page j's heads start in its two rows of
        ``s_buf`` (_scale_rows)."""
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page
        vec = jnp.zeros((1, bk), jnp.float32)
        for j in range(pps):
            vec = jnp.where(
                col_page == j,
                s_buf[slot, j, (at[j] + h) // 128, (at[j] + h) % 128], vec)
        return jnp.where(live, vec, 0.0)

    def heads(h):
        """Kv head ``h``'s query heads in a row of the q and out blocks."""
        return pl.ds(h * group, group)

    def walk(rec, at, rows: int, query, q_pos, q_end):
        """ONE page walk, the call's record ``rec`` (walk_order): compute
        blocks ``[blk0, blk1)`` of table ``tbl``, keys below ``kv_end``
        fetched, for the ``rows`` query rows a kv head from ``at`` on,
        which ``query(h)`` reads out of the q block as ``[rows, w]``.
        ``q_pos`` is their position and ``q_end`` the end of the keys they
        may see — a scalar each for one row's group, ``[rows, 1]`` for a
        tile's rows: the causal (and window) mask is per ROW, and a block
        that lies outside one row's mask (every block, for a row with
        ``q_end`` 0) leaves that row's state as it was.

        While a block is computed the copies of the NEXT one run: the
        walk's own next block, and behind its last block the FIRST block of
        the walk the call takes next (``SUCC``), which then finds its pages
        started (``CARRIED``) and waits for them under its own ``kv_end``.
        A block lands in the half the block before it is not in, whichever
        walk that was of."""
        tbl, blk0, blk1, kv_end, _, half, carried, succ = (
            walk_ref[rec + k] for k in range(WALK))
        nxt = jnp.maximum(succ, 0) * WALK
        succ_tbl, succ_blk0, succ_end = (
            walk_ref[nxt + k] for k in (TBL, BLK0, KV_END))
        rows_at = pl.ds(at, rows)
        last = jnp.minimum(q_pos, q_end - 1)

        def page_id(tbl, blk, j):
            # clamped: a block's last slots may lie past the table's width
            return tbl_ref[tbl, jnp.minimum(blk * pps + j,
                                            tbl_ref.shape[1] - 1)]

        def pages_of(tbl, blk, kv_end, slot, start: bool):
            """Start (or wait for) the copies of block ``blk``'s pages of
            table ``tbl`` into half ``slot``.  Pages past ``kv_end`` are
            never looked up."""
            def page_j(j, _):
                @pl.when((blk * pps + j) * page < kv_end)
                def _page():
                    # a wait needs the copy's shape only, not its source
                    pid = page_id(tbl, blk, j) if start else 0
                    copies = [(kv_hbm.at[pid + base_ref[0]], kv_buf, 0)]
                    if quantized:
                        scale_rows = pl.ds(pid * n_scales // 128, 2)
                        copies += [(s_hbm.at[scale_rows], s_buf, 1)]
                    for src, dst, s in copies:
                        cp = pltpu.make_async_copy(
                            src, dst.at[slot, j], sem.at[s, slot])
                        cp.start() if start else cp.wait()

            # unrolled where it is lowered (a real loop over the pages
            # costs the decode rows 10-34% on the chip), traced once
            jax.lax.fori_loop(0, pps, page_j, None, unroll=True)

        def block(blk, _):
            slot = (half + blk - blk0) % 2
            own = blk + 1 < blk1

            @pl.when(jnp.logical_or(own, succ >= 0))
            def _prefetch():
                pages_of(jnp.where(own, tbl, succ_tbl),
                         jnp.where(own, blk + 1, succ_blk0),
                         jnp.where(own, kv_end, succ_end), 1 - slot, True)

            pages_of(tbl, blk, kv_end, slot, False)
            first = blk * bk
            kv_pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            # q_end lies at or below kv_end: the mask also covers the
            # pages of this block that were not fetched
            mask = kv_pos <= last
            if sliding_window is not None:
                mask = jnp.logical_and(mask, q_pos - kv_pos < sliding_window)
            live_col = kv_pos < kv_end
            live_row = first + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) < kv_end
            if quantized:
                scales_at = [page_id(tbl, blk, j) * n_scales % 128
                             for j in range(pps)]
            for h in range(nkv):
                k_lanes = pl.ds((2 * h if paired else h) * w, w)
                v_lanes = pl.ds((2 * h + 1) * w, w) if paired else k_lanes
                q = query(h).astype(operand)                    # [rows, w]
                # the tile as it lies: a cast only where the page is not in
                # the operands' dtype (a quantized page; float32 operands)
                k = kv_buf[slot, :, :, k_lanes].astype(operand).reshape(
                    bk, w)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [rows, bk]
                if quantized:
                    # q . (k * scale) == (q . k) * scale: dequantize the
                    # scores' columns, not the page
                    s = s * page_scales(slot, scales_at, 2 * h, live_col)
                s = jnp.where(mask, s, NEG_INF)

                m_prev = m_s[h, rows_at, :]                     # [rows, 1]
                m_cur = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                # fully-masked-so-far guard (flash_attention.py:
                # _fwd_kernel): without it exp(NEG_INF - NEG_INF) = 1 would
                # poison the accumulator.  It is also what lets a run walk
                # blocks that only some of its rows see: the others' alpha
                # is 1 and their p is 0
                p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur))
                l_s[h, rows_at, :] = (alpha * l_s[h, rows_at, :]
                                      + jnp.sum(p, axis=1, keepdims=True))
                m_s[h, rows_at, :] = m_cur
                v = k if not paired else kv_buf[
                    slot, :, :, v_lanes].astype(operand).reshape(bk, w)
                # rows past kv_end (pages not fetched, the rest of the last
                # page) hold whatever the buffer held: 0 * NaN would reach
                # the accumulator.  A select on the tile as the matmul takes
                # it; behind a branch that only a walk's last block takes
                # it is no cheaper (PERF.md section 6, PR 45)
                v = jnp.where(live_row, v, jnp.zeros_like(v))
                if quantized:
                    p = p * page_scales(slot, scales_at, 2 * h + 1, live_col)
                acc_s[h, rows_at, :] = (
                    acc_s[h, rows_at, :] * alpha + _weighted_values(p, v))

        @pl.when(carried == 0)
        def _first():
            pages_of(tbl, blk0, kv_end, half, True)

        jax.lax.fori_loop(blk0, blk1, block, None)

    # a row no walk reaches (horizon 0) keeps this state: it writes zeros
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    row0 = i * TILE

    def row_end(r):
        return jnp.minimum(hor_ref[r], pos_ref[r] + 1)

    def span_walk(rec, s):
        """One of the tile's spans (tile_shares): ONE walk, one matmul a kv
        head for all the tile's rows, the rows outside the span masked."""
        first, end, run = (span_ref[(i * 2 + s) * 7 + k] for k in (1, 2, 6))

        def of_a_run():
            # consecutive positions from the first row's on, all live
            q_pos = pos_ref[row0] + jax.lax.broadcasted_iota(
                jnp.int32, (TILE * group, 1), 0) // group
            return q_pos, q_pos + 1

        def of_each_row():
            t = jax.lax.broadcasted_iota(
                jnp.int32, (TILE * group, 1), 0) // group
            q_pos = jnp.zeros_like(t)
            q_end = jnp.zeros_like(t)
            for k in range(TILE):
                inside = jnp.logical_and(first <= k, k < end)
                q_pos = jnp.where(t == k, pos_ref[row0 + k], q_pos)
                q_end = jnp.where(
                    t == k, jnp.where(inside, row_end(row0 + k), 0), q_end)
            return q_pos, q_end

        # the tile's rows at one kv head are whole (8, 128) tiles of
        # float32: the fold to [TILE * group, w] moves nothing.  A run's
        # rows need no look at each row (a chunk's tiles are most of a
        # prompt-heavy tick's)
        walk(rec, 0, TILE * group,
             lambda h: q_ref[:, heads(h), :].reshape(TILE * group, w),
             *jax.lax.cond(run != 0, of_a_run, of_each_row))

    def row_walk(rec, t):
        """A row's own head or tail: ``group`` query rows a matmul.  A tile
        with nothing to share is its rows' tails, their whole walks, one
        after another."""
        r = row0 + t
        walk(rec, pl.multiple_of(t * group, 8), group,
             lambda h: q_ref[t, heads(h), :], pos_ref[r], row_end(r))

    def one(j, _):
        """The tile's ``j``-th walk: the rows' own heads, the spans, then
        the rows' own tails, so that a row meets its blocks in ascending
        order; the empty ones are not in the list."""
        rec = (i * SLOTS + j) * WALK
        slot = walk_ref[rec + SLOT]
        shared = jnp.logical_and(slot >= TILE, slot < TILE + 2)

        @pl.when(shared)
        def _span():
            span_walk(rec, slot - TILE)

        @pl.when(jnp.logical_not(shared))
        def _row():
            row_walk(rec, jnp.where(slot < TILE, slot, slot - TILE - 2))

    jax.lax.fori_loop(0, count_ref[i], one, None)

    l = l_s[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc_s[...] / l_safe
    # back into the rows' own order, head by head
    for h in range(nkv):
        o_ref[:, heads(h), :] = out[h].reshape(TILE, group, w).astype(
            o_ref.dtype)


def _scale_rows(scale):
    """Per-page scales ``[P, H]`` as rows of 128 lanes, ``(page, head)``
    at flat index ``page * H + head``: a copy out of HBM moves whole
    128-lane rows, and a page's heads may straddle two (hence the spare
    row at the end)."""
    flat = scale.reshape(-1)
    rows = pl.cdiv(flat.size, 128) + 1
    return jnp.pad(flat, (0, rows * 128 - flat.size)).reshape(rows, 128)


def _pages_per_step(page_size: int, row_bytes: int) -> int:
    """Pages of one compute block: at least 128 KV tokens, so that a step
    is one full-width score matmul, and at least 64 KiB of rows, so that
    the step's fixed cost (a copy and a wait per page, the loop) is spread
    over enough of them — one K|V pair of 64s then takes 256 tokens a
    step, 8 pairs of 128 take 128 (512 KiB)."""
    return max(1, max(128, (64 << 10) // row_bytes) // page_size)


def _head_lanes(d: int, row: int, latent: bool):
    """How a query head of width ``d`` reads its lanes of a pool row:
    ``(w, paired)`` — ``w`` lanes a matmul operand takes, and whether the
    key's ``w`` lanes are followed by the value's (else the same ``w``
    lanes are both).  A latent row is one head, whole.  A K/V row gives a
    head ``2*d`` lanes, key then value: two aligned slices where ``d`` is
    whole 128-lane groups, else the pair as one operand (``d = 64``)."""
    if latent:
        return row, False
    return (d, True) if d % 128 == 0 else (2 * d, False)


def _paged_call(q, pool, tables, table_index, positions, horizons,
                page_base, *, scale, sliding_window, latent, interpret,
                shares=None):
    """``q`` [R, n_heads, d], one query row a ragged row -> same shape.
    ``pool`` is the flat ``[pages, page, H*d]`` pool of every layer
    (ops/kv_quant.layer_view; quantized: with the calling layer's ``[P,
    H]`` scales) and ``page_base`` the calling layer's first page in it.

    The kernel takes the query and gives the output ROW-MAJOR, ``[rows,
    heads, lanes]``, a program's block its ``TILE`` rows with all their
    heads.  What stands around the call follows from ``g``, ``nkv``, ``d``
    and the dtype alone: where a group is whole sublane tiles and a head
    whole lanes (8 x 128, 16 x 128) the pad and the slice are empty and the
    call's operand and result ARE ``[R, n, d]``; where a group or a head is
    padded (4 -> 8, 71 -> 72, a pair of 64s, a latent row) one pad in front
    and one slice behind stay, and no transposition anywhere."""
    quantized = kv_quant.is_quantized(pool)
    assert not (latent and quantized), "a latent pool is not quantized"
    arr = kv_quant.values_of(pool)
    r, n, d = q.shape
    _, page_size, row = arr.shape
    nkv = 1 if latent else row // (2 * d)
    g = n // nkv
    w, paired = _head_lanes(d, row, latent)
    # a row's group in whole sublane tiles of float32, so that a row of a
    # tile is sliced out of the block where a tile starts; rows in whole
    # TILEs (dead ones behind); the pair read whole (w != d): the query is
    # zero on the value's lanes, so the scores see the key alone
    gp = pl.cdiv(g, 8) * 8
    tiles = pl.cdiv(r, TILE)
    dead = (0, tiles * TILE - r)
    table_index, positions, horizons = (
        jnp.pad(a.astype(jnp.int32), dead)
        for a in (table_index, positions, horizons))
    # the query unscaled (the kernel scales the float32 scores) and in
    # float32, which holds a bf16 query exactly and in which a kv head's
    # ``gp`` rows of a row are whole (8, 128) tiles: one program slices all
    # of a kv head's query rows of its tile out of the row-major block as
    # ONE matmul operand.  The cast fuses into whatever produces ``q``; a
    # block in the query's own dtype, cast once a program into a float32
    # scratch, read the same in the cell and 0.5% slower alone (PERF.md
    # section 6, PR 62)
    qg = jnp.pad(q.astype(jnp.float32).reshape(r, nkv, g, d),
                 (dead, (0, 0), (0, gp - g), (0, w - d)))
    qg = qg.reshape(tiles * TILE, nkv * gp, w)

    def lanes(n):
        return pl.cdiv(n, 128) * 128

    pps = _pages_per_step(page_size, row * arr.dtype.itemsize)
    # who shares a walk: handed in where a caller worked it out once for
    # several calls on the same rows (a tick's layers), else read here
    if shares is None:
        shares = tile_shares(tables, table_index, positions, horizons,
                             window=sliding_window, page=page_size,
                             row_bytes=row * arr.dtype.itemsize)
    assert shares.order.shape == (tiles, SLOTS, WALK), (shares.order.shape, r)
    buf_shape = (2, pps, page_size, row)
    rows = TILE * gp

    tile_spec = pl.BlockSpec((TILE, nkv * gp, w),
                             lambda i, *prefetch: (i, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [tile_spec, hbm_spec]
    operands = [qg, arr]
    scratch = [
        pltpu.VMEM(buf_shape, arr.dtype),
        pltpu.SemaphoreType.DMA((2 if quantized else 1, 2)),
        pltpu.VMEM((nkv, rows, 1), jnp.float32),
        pltpu.VMEM((nkv, rows, 1), jnp.float32),
        pltpu.VMEM((nkv, rows, w), jnp.float32),
    ]
    if quantized:
        in_specs += [hbm_spec]
        operands += [_scale_rows(pool.scale)]
        scratch += [pltpu.SMEM((2, pps, 2, 128), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(tiles,),
        in_specs=in_specs,
        out_specs=tile_spec,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_kernel, group=gp, scale=scale,
        operand=_operand_dtype(q.dtype, arr.dtype, quantized),
        sliding_window=sliding_window, quantized=quantized, paired=paired,
    )

    # VMEM, every last dim padded to 128 lanes: the float32 q and the out
    # blocks (row-major, two of each, the pipeline's; the out block is
    # stored into a kv head at a time and wants no scratch of its own), the
    # softmax state of the whole
    # tile, both halves of the page buffer, and a step's [rows, block] fp32
    # temporaries (scores, probabilities, masks, and the probabilities' two
    # bf16 halves: one more) at a run's TILE * group rows.  A tile of 8
    # needs 4.4 MiB at Command A+'s widths (128 rows a kv head x 8), 5.8 at
    # Falcon's (576 rows, blocks of 256 tokens), 3.9 at the latent row's and
    # 2.7 at Mistral's; a whole 64-row chunk a program would need 29 at
    # Command A+'s and 45 at Falcon's, over Mosaic's default of 16 — the
    # tile is what keeps every geometry under it, and the limit is stated
    # all the same
    vmem = (2 * nkv * rows * lanes(w) * (4 + q.dtype.itemsize)
            + nkv * rows * (2 * 128 + lanes(w)) * 4
            + math.prod(buf_shape) * arr.dtype.itemsize
            + 7 * rows * lanes(pps * page_size) * 4)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * TILE, nkv * gp, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(2 * vmem, 16 << 20)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), positions, horizons,
      shares.spans.reshape(-1), shares.order.reshape(-1), shares.count,
      jnp.asarray(page_base, jnp.int32).reshape(1), *operands)
    # what was padded is sliced off again; the pair read whole: its value
    # lanes are the output
    return out.reshape(tiles * TILE, nkv, gp, w)[
        :r, :, :g, (0 if w == d or latent else d):].reshape(r, n, -1)


def paged_ragged_kernel(
    q: jax.Array,             # [R, 1, n_heads, d]
    pool,                     # [pages, page_size, H*d] (kv_quant's row)
    tables: jax.Array,        # [T, max_pages_per_seq] int32 unique tables
    table_index: jax.Array,   # [R] int32 row -> table
    positions: jax.Array,     # [R] int32
    horizons: jax.Array,      # [R] int32 bucketed kv horizon (0 = dead)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    latent: bool = False,
    page_base=0,
    interpret: bool = False,
    shares: Optional[TileShares] = None,
) -> jax.Array:
    """ONE launch for a whole ragged tick; returns [R, 1, n_heads, d].
    ``shares``: :func:`tile_shares` of these very tables and rows under
    this window and this pool's row, worked out once by a caller that
    makes several such calls (None: worked out here)."""
    return _paged_call(
        q[:, 0], pool, tables, table_index, positions, horizons, page_base,
        scale=scale, sliding_window=sliding_window, latent=latent,
        interpret=interpret, shares=shares)[:, None]


def paged_prefill_kernel(
    q: jax.Array,             # [b, s, n_heads, d]
    pool,
    block_tables: jax.Array,  # [b, kv_pages] int32 (chunk horizon)
    start: jax.Array,         # [b] int32 — position of q[:, 0]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    latent: bool = False,
    page_base=0,
    interpret: bool = False,
) -> jax.Array:
    """One prefill chunk; returns [b, s, n_heads, d].  A chunk is ``s``
    ragged rows of one table at consecutive positions: runs."""
    b, s, n, d = q.shape
    positions = (start[:, None] + jnp.arange(s, dtype=jnp.int32)).reshape(-1)
    return paged_ragged_kernel(
        q.reshape(b * s, 1, n, d), pool, block_tables,
        jnp.repeat(jnp.arange(b, dtype=jnp.int32), s), positions,
        jnp.repeat(start + s, s), scale=scale,
        sliding_window=sliding_window, latent=latent, page_base=page_base,
        interpret=interpret).reshape(b, s, n, d)


def paged_decode_kernel(
    q: jax.Array,             # [b, 1, n_heads, d]
    pool,
    block_tables: jax.Array,  # [b, max_pages_per_seq] int32
    positions: jax.Array,     # [b] int32
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    latent: bool = False,
    page_base=0,
    interpret: bool = False,
) -> jax.Array:
    """One decode step; returns [b, 1, n_heads, d]."""
    b = q.shape[0]
    return paged_ragged_kernel(
        q, pool, block_tables, jnp.arange(b, dtype=jnp.int32),
        positions, positions + 1, scale=scale,
        sliding_window=sliding_window, latent=latent, page_base=page_base,
        interpret=interpret)
