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
online-softmax update per kv head for the whole block.  The last block of
a walk is partial: pages past the context are not fetched, their columns
are masked and their value rows zeroed.  The softmax state (running max m,
normalizer l, fp32 accumulator) of the tile's rows lives in VMEM scratch —
the blockwise scheme of ops/pallas/flash_attention.py with blocks of pages
as KV blocks.  GQA is native (q grouped [tiles, nkv, TILE*group, d], no
K/V expansion).

WHO SHARES A WALK is read from the call's own data (:func:`tile_runs`, the
one rule; the engine counts by it too): a tile whose rows are ONE RUN — all
live, on one table, at consecutive positions: a prompt chunk's rows, a
verify block that fills a tile — is walked ONCE, from its first row's
window to its last row's position, with one score matmul and one value
matmul a kv head for all ``TILE * group`` query rows, the causal (and
window) mask and the softmax state per row.  A block outside one row's mask
leaves that row's state as it was (alpha 1, p 0), so each row's result is
what a walk of its own gives.  Any other tile — decode rows, a table each;
a tile where one request's rows end and the next one's begin — walks its
live rows one after another inside the program, ``group`` query rows a
matmul: a row alone costs what it cost when the grid ran over rows.  Which
of the two a tile takes is data, so a tick's composition never recompiles.

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
from typing import Optional

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
    """THE grouping rule, a pure function of the call's data: which tiles
    of ``TILE`` consecutive rows the kernel serves by one page walk.

    ``[R]`` arrays (numpy, on the host; traced, in front of the kernel) ->
    ``(shared, live)``, both ``[ceil(R / TILE)]``: ``shared[i]`` says that
    tile ``i`` is ONE RUN — every row live (its horizon past its position),
    all on one table, at consecutive positions — and ``live[i]`` counts its
    rows with a horizon.  A run costs one walk, any other tile one walk a
    live row: ``where(shared, 1, live)``.  Rows past the last whole tile
    count as dead ones (the wrapper pads with them)."""
    xp = jnp if any(isinstance(a, jax.Array)
                    for a in (table_index, positions, horizons)) else np
    pad = (0, -table_index.shape[0] % TILE)
    idx, pos, hor = (xp.pad(a, pad).reshape(-1, TILE)
                     for a in (table_index, positions, horizons))
    run = ((hor > pos) & (idx == idx[:, :1])
           & (pos - pos[:, :1] == np.arange(TILE)))
    return run.all(axis=1), (hor > 0).sum(axis=1)


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
    idx_ref,     # [b] int32 row -> table
    pos_ref,     # [b] int32 the row's position
    hor_ref,     # [b] int32 kv horizon in tokens (0 = dead row)
    run_ref,     # [b / TILE] int32: the tile is one run (tile_runs)
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
    """One program per TILE of rows: ``group`` query rows a kv head and
    row (the heads of its group, padded to whole sublane tiles), UNSCALED
    and in float32 — an exact copy of a bf16 query, whose rows are sliced
    where a 32-bit tile starts and cast to ``operand``, the dtype both
    matmuls take their operands in (``_operand_dtype``), beside the matmul.
    ``paired``: a head's ``w`` key lanes are followed by its ``w`` value
    lanes; otherwise its ``w`` lanes are key and value at once (a latent
    row; a K|V pair of 64s)."""
    if quantized:
        (q_ref, kv_hbm, s_hbm, o_ref,
         kv_buf, sem, m_s, l_s, acc_s, s_buf) = refs
    else:
        q_ref, kv_hbm, o_ref, kv_buf, sem, m_s, l_s, acc_s = refs
    i = pl.program_id(0)
    nkv, _, w = q_ref.shape
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

    def walk(at, rows: int, tbl, pos0, kv_end):
        """ONE page walk for the ``rows`` query rows a kv head from ``at``
        on: row ``r`` of them stands at position ``pos0 + r // group`` of
        table ``tbl`` — the causal (and window) mask is per ROW.  Keys
        ``[kv_start, kv_end)`` are all any of them can see; a block that
        lies outside one row's mask leaves that row's state as it was."""
        kv_start = (0 if sliding_window is None
                    else jnp.maximum(pos0 - sliding_window + 1, 0))
        blk0 = kv_start // bk
        blk1 = (kv_end + bk - 1) // bk
        rows_at = pl.ds(at, rows)

        def page_id(blk, j):
            # clamped: a block's last slots may lie past the table's width
            return tbl_ref[tbl, jnp.minimum(blk * pps + j,
                                            tbl_ref.shape[1] - 1)]

        def pages_of(blk, slot, start: bool):
            """Start (or wait for) the copies of block ``blk``'s pages into
            half ``slot``.  Pages past ``kv_end`` are never looked up."""
            def page_j(j, _):
                @pl.when((blk * pps + j) * page < kv_end)
                def _page():
                    # a wait needs the copy's shape only, not its source
                    pid = page_id(blk, j) if start else 0
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
            slot = blk % 2

            @pl.when(blk + 1 < blk1)
            def _prefetch():
                pages_of(blk + 1, 1 - slot, True)

            pages_of(blk, slot, False)
            first = blk * bk
            q_pos = pos0
            if rows > group:
                q_pos += jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0) // group
            kv_pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            # kv_end also covers the pages of this block that were not
            # fetched
            mask = kv_pos <= jnp.minimum(q_pos, kv_end - 1)
            if sliding_window is not None:
                mask = jnp.logical_and(mask, q_pos - kv_pos < sliding_window)
            live_col = kv_pos < kv_end
            live_row = first + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) < kv_end
            if quantized:
                scales_at = [page_id(blk, j) * n_scales % 128
                             for j in range(pps)]
            for h in range(nkv):
                k_lanes = pl.ds((2 * h if paired else h) * w, w)
                v_lanes = pl.ds((2 * h + 1) * w, w) if paired else k_lanes
                q = q_ref[h, rows_at, :].astype(operand)        # [rows, w]
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

        @pl.when(blk1 > blk0)
        def _walk():
            pages_of(blk0, blk0 % 2, True)
            jax.lax.fori_loop(blk0, blk1, block, None)

    # a row no walk reaches (horizon 0) keeps this state: it writes zeros
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    row0 = i * TILE

    @pl.when(run_ref[i] != 0)
    def _run():
        # the tile is one run: one walk, one matmul a kv head for all its
        # rows, from its first row's window to its last row's position
        walk(0, TILE * group, idx_ref[row0], pos_ref[row0],
             pos_ref[row0] + TILE)

    @pl.when(run_ref[i] == 0)
    def _rows():
        def row(t, _):
            r = row0 + t
            walk(pl.multiple_of(t * group, 8), group, idx_ref[r], pos_ref[r],
                 jnp.minimum(hor_ref[r], pos_ref[r] + 1))

        jax.lax.fori_loop(0, TILE, row, None)

    l = l_s[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_s[...] / l_safe).astype(o_ref.dtype)


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
                page_base, *, scale, sliding_window, latent, interpret):
    """``q`` [R, n_heads, d], one query row a ragged row -> same shape.
    ``pool`` is the flat ``[pages, page, H*d]`` pool of every layer
    (ops/kv_quant.layer_view; quantized: with the calling layer's ``[P,
    H]`` scales) and ``page_base`` the calling layer's first page in it."""
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
    run, _ = tile_runs(table_index, positions, horizons)
    # kv-head-major query rows, unscaled (the kernel scales the float32
    # scores) and in float32, which holds a bf16 query exactly: one program
    # sees all of a kv head's query rows of its tile as ONE matmul operand.
    # A bf16 block would want a row's group in tiles of 16 rows: measured,
    # the padded rows cost more than this copy (PERF.md section 6, PR 45)
    qg = jnp.pad(q.astype(jnp.float32).reshape(r, nkv, g, d),
                 (dead, (0, 0), (0, gp - g), (0, w - d)))
    qg = qg.reshape(tiles, TILE, nkv, gp, w).transpose(0, 2, 1, 3, 4)

    def lanes(n):
        return pl.cdiv(n, 128) * 128

    pps = _pages_per_step(page_size, row * arr.dtype.itemsize)
    buf_shape = (2, pps, page_size, row)
    rows = TILE * gp

    tile_spec = pl.BlockSpec((None, nkv, rows, w),
                             lambda i, *prefetch: (i, 0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [tile_spec, hbm_spec]
    operands = [qg.reshape(tiles, nkv, rows, w), arr]
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
        num_scalar_prefetch=6,
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
    # blocks (two of each, the pipeline's), the softmax state of the whole
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
        out_shape=jax.ShapeDtypeStruct((tiles, nkv, rows, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(2 * vmem, 16 << 20)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), table_index, positions, horizons,
      run.astype(jnp.int32), jnp.asarray(page_base, jnp.int32).reshape(1),
      *operands)
    out = out.reshape(tiles, nkv, TILE, gp, w).transpose(0, 2, 1, 3, 4)
    # the pair read whole: its value lanes are the output
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
) -> jax.Array:
    """ONE launch for a whole ragged tick; returns [R, 1, n_heads, d]."""
    return _paged_call(
        q[:, 0], pool, tables, table_index, positions, horizons, page_base,
        scale=scale, sliding_window=sliding_window, latent=latent,
        interpret=interpret)[:, None]


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
