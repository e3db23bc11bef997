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

Layout rules (Mosaic).  A copy out of HBM moves whole 128-lane rows, so
the pool ``[P, page, nkv, d]`` is read through its contiguous view ``[P,
page, nkv*d]`` and a head's ``d`` lanes are sliced out of the copy in
VMEM — legal when ``d % 128 == 0``; a single head narrower than that
(Falcon-7B: one of 64) has its row padded to 128 lanes first, which is what
its tiles in HBM hold anyway (ops/paged_attention._kernel_refusal is the
whole rule).  Per-page scales ``[P, nkv]`` are read as 128-lane rows of
their flat view into SMEM, one copy per fetched page.

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
    # q block, the pools in HBM [, their scales], out block, then scratch
    *refs,
    scale: float,
    group: int,
    sliding_window: Optional[int],
    quantized: bool,
    shared_kv: bool = False,
):
    """One program per sequence: ``rows = s*group`` query rows per kv head,
    row ``r`` at position ``pos0 + r // group`` — the causal mask is per ROW.
    Decode and ragged calls have ``s == 1``.  ``shared_kv`` (a latent pool):
    the key pages are the value pages, copied once."""
    if shared_kv:
        q_ref, k_hbm, o_ref, k_buf, sem, m_s, l_s, acc_s = refs
        v_hbm, v_buf = None, k_buf
    elif quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, sem, m_s, l_s, acc_s, ks_buf, vs_buf) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, m_s, l_s, acc_s = refs
    i = pl.program_id(0)
    nkv, rows, d = q_ref.shape
    _, pps, page, _ = k_buf.shape
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
                copies = [(k_hbm.at[pid], k_buf, 0)]
                if not shared_kv:
                    copies += [(v_hbm.at[pid], v_buf, 1)]
                if quantized:
                    scale_rows = pl.ds(pid * nkv // 128, 2)
                    copies += [(ks_hbm.at[scale_rows], ks_buf, 2),
                               (vs_hbm.at[scale_rows], vs_buf, 3)]
                for src, dst, s in copies:
                    cp = pltpu.make_async_copy(
                        src, dst.at[slot, j], sem.at[s, slot])
                    cp.start() if start else cp.wait()

    def page_scales(buf, slot, at, h, live):
        """[1, bk] row of head ``h``'s per-page scales, 0 where not live;
        ``at[j]`` is where page j's heads start in its two rows of ``buf``
        (_scale_rows)."""
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page
        vec = jnp.zeros((1, bk), jnp.float32)
        for j in range(pps):
            vec = jnp.where(
                col_page == j,
                buf[slot, j, (at[j] + h) // 128, (at[j] + h) % 128], vec)
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
            scales_at = [page_id(blk, j) * nkv % 128 for j in range(pps)]
        for h in range(nkv):
            lanes = pl.ds(h * d, d)
            q = q_ref[h].astype(jnp.float32) * scale            # [rows, d]
            k = k_buf[slot, :, :, lanes].astype(jnp.float32).reshape(bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [rows, bk]
            if quantized:
                # q . (k * scale) == (q . k) * scale: dequantize the
                # scores' columns, not the page
                s = s * page_scales(ks_buf, slot, scales_at, h, live_col)
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
            v = v_buf[slot, :, :, lanes].astype(jnp.float32).reshape(bk, d)
            # rows of pages not fetched hold whatever the buffer held:
            # 0 * NaN would reach the accumulator
            v = jnp.where(live_row, v, 0.0)
            if quantized:
                p = p * page_scales(vs_buf, slot, scales_at, h, live_col)
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
    """Per-page scales ``[P, nkv]`` as rows of 128 lanes, ``(page, head)``
    at flat index ``page * nkv + head``: a copy out of HBM moves whole
    128-lane rows, and a page's heads may straddle two (hence the spare
    row at the end)."""
    flat = scale.reshape(-1)
    rows = pl.cdiv(flat.size, 128) + 1
    return jnp.pad(flat, (0, rows * 128 - flat.size)).reshape(rows, 128)


def _pages_per_step(page_size: int, row_bytes: int) -> int:
    """Pages of one compute block: at least 128 KV tokens, so that a step
    is one full-width score matmul, and at least 64 KiB of K, so that the
    step's fixed cost (a copy and a wait per page, the loop) is spread
    over enough of them — one kv head of 64 then takes 256 tokens a step,
    8 kv heads of 128 take 128 (256 KiB)."""
    return max(1, max(128, (64 << 10) // row_bytes) // page_size)


def _paged_call(qg, k_pool, v_pool, tables, table_index, positions, horizons,
                *, group, scale, sliding_window, interpret):
    """``qg`` [b, nkv, rows, d] kv-head-major query rows -> same shape.
    ``v_pool=None``: the key pool is the value pool (a latent pool)."""
    quantized = kv_quant.is_quantized(k_pool)
    shared_kv = v_pool is None
    assert not (shared_kv and quantized), "a latent pool is not quantized"
    if shared_kv:
        v_pool = k_pool
    k_arr, v_arr = (k_pool.q, v_pool.q) if quantized else (k_pool, v_pool)
    b, nkv, rows, d = qg.shape
    num_pages, page_size, _, _ = k_arr.shape

    def lanes(n):
        return pl.cdiv(n, 128) * 128

    # a copy out of HBM moves whole 128-lane rows: a narrower page row (one
    # kv head of 64) is padded to 128 lanes, which is what its tiles in HBM
    # hold anyway
    width = lanes(nkv * d)
    pps = _pages_per_step(page_size, width * k_arr.dtype.itemsize)

    def view(pool):
        flat = pool.reshape(num_pages, page_size, nkv * d)
        return jnp.pad(flat, ((0, 0), (0, 0), (0, width - nkv * d)))

    buf_shape = (2, pps, page_size, width)

    row_spec = pl.BlockSpec((None, nkv, rows, d),
                            lambda i, tbl, idx, pos, hor: (i, 0, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [row_spec, hbm_spec] + [hbm_spec] * (not shared_kv)
    operands = [qg, view(k_arr)] + [view(v_arr)] * (not shared_kv)
    scratch = [pltpu.VMEM(buf_shape, k_arr.dtype)] + [
        pltpu.VMEM(buf_shape, v_arr.dtype)] * (not shared_kv) + [
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
        pltpu.VMEM((nkv, rows, 1), jnp.float32),
        pltpu.VMEM((nkv, rows, 1), jnp.float32),
        pltpu.VMEM((nkv, rows, d), jnp.float32),
    ]
    if quantized:
        in_specs += [hbm_spec, hbm_spec]
        operands += [_scale_rows(k_pool.scale), _scale_rows(v_pool.scale)]
        scratch += [pltpu.SMEM((2, pps, 2, 128), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, group=group,
        sliding_window=sliding_window, quantized=quantized,
        shared_kv=shared_kv,
    )

    # VMEM, every last dim padded to 128 lanes: the q and out blocks (two
    # of each, the pipeline's), the softmax state, both halves of the K
    # and V buffers, and a step's [rows, block] fp32 temporaries (scores,
    # probabilities, masks).  A ragged tick needs 1.1 MiB at Mistral's
    # widths and 0.9 at Falcon's; Falcon's 64-row chunk (4544 rows a kv
    # head) 38 MiB, over Mosaic's default of 16 — so the limit is stated
    vmem = (4 * nkv * rows * lanes(d) * qg.dtype.itemsize
            + nkv * rows * (2 * 128 + lanes(d)) * 4
            + 2 * math.prod(buf_shape) * k_arr.dtype.itemsize
            + 6 * rows * lanes(pps * page_size) * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(2 * vmem, 16 << 20)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), table_index.astype(jnp.int32),
      positions.astype(jnp.int32), horizons.astype(jnp.int32), *operands)


def _nkv(k_pool) -> int:
    return (k_pool.q if kv_quant.is_quantized(k_pool) else k_pool).shape[2]


def paged_ragged_kernel(
    q: jax.Array,             # [R, 1, n_heads, d]
    k_pool,                   # [num_pages, page_size, n_kv_heads, d]
    v_pool,
    tables: jax.Array,        # [T, max_pages_per_seq] int32 unique tables
    table_index: jax.Array,   # [R] int32 row -> table
    positions: jax.Array,     # [R] int32
    horizons: jax.Array,      # [R] int32 bucketed kv horizon (0 = dead)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """ONE launch for a whole ragged tick; returns [R, 1, n_heads, d]."""
    b, _, n, d = q.shape
    nkv = _nkv(k_pool)
    g = n // nkv
    out = _paged_call(
        q.reshape(b, nkv, g, d), k_pool, v_pool, tables, table_index,
        positions, horizons, group=g, scale=scale,
        sliding_window=sliding_window, interpret=interpret)
    return out.reshape(b, 1, n, d)


def paged_prefill_kernel(
    q: jax.Array,             # [b, s, n_heads, d]
    k_pool,
    v_pool,
    block_tables: jax.Array,  # [b, kv_pages] int32 (chunk horizon)
    start: jax.Array,         # [b] int32 — position of q[:, 0]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """One prefill chunk; returns [b, s, n_heads, d]."""
    b, s, n, d = q.shape
    nkv = _nkv(k_pool)
    g = n // nkv
    # kv-head-major query rows: one grid step sees all of a kv head's
    # query rows for the chunk
    qg = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4)
    out = _paged_call(
        qg.reshape(b, nkv, s * g, d), k_pool, v_pool, block_tables,
        jnp.arange(b, dtype=jnp.int32), start, start + s, group=g,
        scale=scale, sliding_window=sliding_window, interpret=interpret)
    return out.reshape(b, nkv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, s, n, d)


def paged_decode_kernel(
    q: jax.Array,             # [b, 1, n_heads, d]
    k_pool,
    v_pool,
    block_tables: jax.Array,  # [b, max_pages_per_seq] int32
    positions: jax.Array,     # [b] int32
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """One decode step; returns [b, 1, n_heads, d]."""
    b = q.shape[0]
    return paged_ragged_kernel(
        q, k_pool, v_pool, block_tables, jnp.arange(b, dtype=jnp.int32),
        positions, positions + 1, scale=scale,
        sliding_window=sliding_window, interpret=interpret)
