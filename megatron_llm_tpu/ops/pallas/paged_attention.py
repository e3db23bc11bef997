"""Pallas TPU paged-attention kernel (Ragged Paged Attention style).

One kernel body serves the engine's three call shapes over the same page
pool: a DECODE step (one query row per sequence), a PREFILL CHUNK (s query
rows of one sequence against its block-tabled prefix) and a RAGGED tick
(decode, verify and prefill rows flattened to single-token rows, each with
its own table index and kv horizon).  The [b, max_pages*page_size] gather
of the jnp path (ops/paged_attention.py) never materializes.

The grid runs over rows only; the pools stay in HBM and the block tables,
table indices, positions and horizons are *scalar-prefetch* operands in
SMEM.  Inside one program a loop walks that row's context in COMPUTE
BLOCKS of several pages (_pages_per_step: 128 tokens or more), from the
first block the sliding window still sees to the one that holds
``min(horizon, last position + 1)``: the trip count is data, so table
slots past a row's context are never looked up, a dead row (horizon 0)
runs zero trips and writes zeros, and the cost of a call does not depend on
the table's width (``engine_max_seq``).  Each step starts the copies of
the NEXT block's pages (``page_id = tables[table_index[row], j]``, one copy
a page, all kv heads of the page at once) into the other half of a
double-buffered VMEM scratch, waits for the current half, and does one
score matmul and one online-softmax update per kv head for the whole
block.  The last block of a row is partial: pages past the context are not
fetched, their columns are masked and their value rows zeroed.  The
softmax state (running max m, normalizer l, fp32 accumulator) lives in
VMEM scratch across the steps of one row — the blockwise scheme of
ops/pallas/flash_attention.py with blocks of pages as KV blocks.  GQA is
native (q grouped [b, nkv, rows*group, d], no K/V expansion).

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
to the query dtype.  Quantized pools (ops/kv_quant.QuantPagedKV) arrive in
their storage dtype; the int8/fp8 -> fp32 cast and the scale multiply (on
the scores' and probabilities' columns: ``q . (k*s) == (q . k)*s``) happen
inside the step that consumes the page, so HBM traffic is the quantized
bytes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops import kv_quant

NEG_INF = -1e30


def _paged_kernel(
    # scalar prefetch — all traced data, so one compiled launch serves any
    # tick composition
    tbl_ref,     # [T, max_pages] int32 block tables
    idx_ref,     # [b] int32 row -> table
    pos_ref,     # [b] int32 position of the row's first query
    hor_ref,     # [b] int32 kv horizon in tokens (0 = dead row)
    base_ref,    # [1] int32 first page of the calling layer in the pool
    # q block, the pool in HBM [, its scales], out block, then scratch
    *refs,
    scale: float,
    group: int,
    sliding_window: Optional[int],
    quantized: bool,
    paired: bool,
):
    """One program per sequence: ``rows = s*group`` query rows per kv head,
    row ``r`` at position ``pos0 + r // group`` — the causal mask is per ROW.
    Decode and ragged calls have ``s == 1``.  ``paired``: a head's ``w``
    key lanes are followed by its ``w`` value lanes; otherwise its ``w``
    lanes are key and value at once (a latent row; a K|V pair of 64s)."""
    if quantized:
        (q_ref, kv_hbm, s_hbm, o_ref,
         kv_buf, sem, m_s, l_s, acc_s, s_buf) = refs
    else:
        q_ref, kv_hbm, o_ref, kv_buf, sem, m_s, l_s, acc_s = refs
    i = pl.program_id(0)
    nkv, rows, w = q_ref.shape
    _, pps, page, _ = kv_buf.shape
    bk = pps * page
    tbl = idx_ref[i]
    pos0 = pos_ref[i]
    # keys [kv_start, kv_end) are all any row of this program can see; a
    # dead row (horizon 0) has none
    kv_end = jnp.minimum(hor_ref[i], pos0 + rows // group)
    kv_start = (0 if sliding_window is None
                else jnp.maximum(pos0 - sliding_window + 1, 0))
    blk0 = kv_start // bk
    blk1 = (kv_end + bk - 1) // bk
    # storage heads a page's scale row holds: a key and a value per head
    n_scales = 2 * nkv

    def page_id(blk, j):
        # clamped: a block's last slots may lie past the table's width
        return tbl_ref[tbl, jnp.minimum(blk * pps + j, tbl_ref.shape[1] - 1)]

    def pages_of(blk, slot, start: bool):
        """Start (or wait for) the copies of block ``blk``'s pages into
        half ``slot``.  Pages past ``kv_end`` are never looked up."""
        for j in range(pps):
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

    def page_scales(slot, at, h, live):
        """[1, bk] row of storage head ``h``'s per-page scales, 0 where
        not live; ``at[j]`` is where page j's heads start in its two rows
        of ``s_buf`` (_scale_rows)."""
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page
        vec = jnp.zeros((1, bk), jnp.float32)
        for j in range(pps):
            vec = jnp.where(
                col_page == j,
                s_buf[slot, j, (at[j] + h) // 128, (at[j] + h) % 128], vec)
        return jnp.where(live, vec, 0.0)

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
        # kv_end also covers the pages of this block that were not fetched
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
            q = q_ref[h].astype(jnp.float32) * scale            # [rows, w]
            k = kv_buf[slot, :, :, k_lanes].astype(jnp.float32).reshape(
                bk, w)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [rows, bk]
            if quantized:
                # q . (k * scale) == (q . k) * scale: dequantize the
                # scores' columns, not the page
                s = s * page_scales(slot, scales_at, 2 * h, live_col)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_s[h]                                     # [rows, 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # fully-masked-so-far guard (flash_attention.py:_fwd_kernel):
            # without it exp(NEG_INF - NEG_INF) = 1 would poison the
            # accumulator
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur))
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            m_s[h] = m_cur
            v = k if not paired else kv_buf[
                slot, :, :, v_lanes].astype(jnp.float32).reshape(bk, w)
            # rows of pages not fetched hold whatever the buffer held:
            # 0 * NaN would reach the accumulator
            v = jnp.where(live_row, v, 0.0)
            if quantized:
                p = p * page_scales(slot, scales_at, 2 * h + 1, live_col)
            acc_s[h] = acc_s[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(blk1 > blk0)
    def _walk():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        pages_of(blk0, blk0 % 2, True)
        jax.lax.fori_loop(blk0, blk1, block, None)
        l = l_s[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_s[...] / l_safe).astype(o_ref.dtype)

    @pl.when(blk1 <= blk0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


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


def _paged_call(qg, pool, tables, table_index, positions, horizons,
                page_base, *, group, scale, sliding_window, latent,
                interpret):
    """``qg`` [b, nkv, rows, d] kv-head-major query rows -> same shape.
    ``pool`` is the flat ``[pages, page, H*d]`` pool of every layer
    (ops/kv_quant.layer_view; quantized: with the calling layer's ``[P,
    H]`` scales) and ``page_base`` the calling layer's first page in it."""
    quantized = kv_quant.is_quantized(pool)
    assert not (latent and quantized), "a latent pool is not quantized"
    arr = kv_quant.values_of(pool)
    b, nkv, rows, d = qg.shape
    _, page_size, row = arr.shape
    w, paired = _head_lanes(d, row, latent)
    if w != d:
        # the pair read whole: the query is zero on the value's lanes,
        # so the scores see the key alone
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, w - d),))

    def lanes(n):
        return pl.cdiv(n, 128) * 128

    pps = _pages_per_step(page_size, row * arr.dtype.itemsize)
    buf_shape = (2, pps, page_size, row)

    row_spec = pl.BlockSpec((None, nkv, rows, w),
                            lambda i, tbl, idx, pos, hor, base: (i, 0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [row_spec, hbm_spec]
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
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, group=group,
        sliding_window=sliding_window, quantized=quantized, paired=paired,
    )

    # VMEM, every last dim padded to 128 lanes: the q and out blocks (two
    # of each, the pipeline's), the softmax state, both halves of the page
    # buffer, and a step's [rows, block] fp32 temporaries (scores,
    # probabilities, masks).  A ragged tick needs 1.1 MiB at Mistral's
    # widths and 0.9 at Falcon's; Falcon's 64-row chunk (4544 rows a kv
    # head) 38 MiB, over Mosaic's default of 16 — so the limit is stated
    vmem = (4 * nkv * rows * lanes(w) * qg.dtype.itemsize
            + nkv * rows * (2 * 128 + lanes(w)) * 4
            + math.prod(buf_shape) * arr.dtype.itemsize
            + 6 * rows * lanes(pps * page_size) * 4)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(2 * vmem, 16 << 20)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), table_index.astype(jnp.int32),
      positions.astype(jnp.int32), horizons.astype(jnp.int32),
      jnp.asarray(page_base, jnp.int32).reshape(1), *operands)
    # the pair read whole: its value lanes are the output
    return out if w == d or latent else out[..., d:]


def _nkv(pool, d: int, latent: bool) -> int:
    return 1 if latent else kv_quant.row_width(pool) // (2 * d)


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
    b, _, n, d = q.shape
    nkv = _nkv(pool, d, latent)
    g = n // nkv
    out = _paged_call(
        q.reshape(b, nkv, g, d), pool, tables, table_index,
        positions, horizons, page_base, group=g, scale=scale,
        sliding_window=sliding_window, latent=latent, interpret=interpret)
    return out.reshape(b, 1, n, d)


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
    """One prefill chunk; returns [b, s, n_heads, d]."""
    b, s, n, d = q.shape
    nkv = _nkv(pool, d, latent)
    g = n // nkv
    # kv-head-major query rows: one grid step sees all of a kv head's
    # query rows for the chunk
    qg = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4)
    out = _paged_call(
        qg.reshape(b, nkv, s * g, d), pool, block_tables,
        jnp.arange(b, dtype=jnp.int32), start, start + s, page_base,
        group=g, scale=scale, sliding_window=sliding_window, latent=latent,
        interpret=interpret)
    return out.reshape(b, nkv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, s, n, d)


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
