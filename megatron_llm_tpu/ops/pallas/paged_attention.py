"""Pallas TPU paged-attention kernel (Ragged Paged Attention style).

One kernel body serves the engine's three call shapes over the same page
pool: a DECODE step (one query row per sequence), a PREFILL CHUNK (s query
rows of one sequence against its block-tabled prefix) and a RAGGED tick
(decode, verify and prefill rows flattened to single-token rows, each with
its own table index and kv horizon).  The block table is a *scalar-prefetch*
operand (pltpu.PrefetchScalarGridSpec), so the BlockSpec index map resolves
``page_id = tables[table_index[row], j]`` before the grid step runs and the
pipeline DMAs exactly that page from the HBM pool into VMEM — the
[b, max_pages*page_size] gather of the jnp path (ops/paged_attention.py)
never materializes.

Grid ``(rows, n_kv_heads, max_pages_per_seq)``, pages innermost: on TPU the
grid is a sequential loop, so the online-softmax state (running max m,
normalizer l, fp32 accumulator) lives in VMEM scratch and carries across
page iterations of one (row, kv-head) pair — the same blockwise scheme as
ops/pallas/flash_attention.py, with pages playing the role of KV blocks.
Pages past a row's context are skipped with @pl.when; GQA is native (q
grouped [b, nkv, rows*group, d], no K/V expansion).

Layout rule (Mosaic): the last two dims of a block must be (8k, 128k) or
the array's own.  The pool ``[P, page, nkv, d]`` is therefore read through
its contiguous view ``[P, page, nkv*d]`` with a ``(page, d)`` block at lane
offset ``h*d`` — legal when ``d % 128 == 0`` (or ``nkv == 1``), see
ops/paged_attention._kernel_ok.  Per-page scales ``[P, nkv]`` ride as a
``(1, nkv)`` SMEM block of ``[P, 1, nkv]``; the step reads scalar ``h``.

Numerics match the jnp path: fp32 logits/softmax/accumulator, outputs cast
to the query dtype.  Quantized pools (ops/kv_quant.QuantPagedKV) arrive in
their storage dtype; the int8/fp8 -> fp32 cast and the scale multiply
happen inside the step that consumes the page, so HBM traffic is the
quantized bytes.
"""

from __future__ import annotations

import functools
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
    # tensor refs: q, k-page, v-page [, k-scale, v-scale], out + scratch
    *refs,
    scale: float,
    page_size: int,
    group: int,
    sliding_window: Optional[int],
    quantized: bool,
):
    """``rows = s*group`` query rows per (sequence, kv-head) pair, row ``r``
    at position ``pos0 + r // group`` — the causal mask is per ROW.  Decode
    and ragged calls have ``s == 1``."""
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs
    i = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    first = j * page_size
    pos0 = pos_ref[i]
    rows = q_ref.shape[0]
    last_pos = pos0 + rows // group - 1

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # first < hor kills dead rows (horizon 0): they touch no page at all
    run = jnp.logical_and(first <= last_pos, first < hor_ref[i])
    if sliding_window is not None:
        # page entirely below every query row's window -> skip
        run = jnp.logical_and(
            run, first + page_size > pos0 - sliding_window + 1)

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale          # [rows, d]
        k = k_ref[...].astype(jnp.float32)                  # [page, d]
        if quantized:
            k = k * ks_ref[0, h]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, page]
        kv_pos = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        q_pos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        mask = kv_pos <= q_pos
        if sliding_window is not None:
            mask = jnp.logical_and(mask, q_pos - kv_pos < sliding_window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]                                   # [rows, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # fully-masked-so-far guard (flash_attention.py:_fwd_kernel): without
        # it exp(NEG_INF - NEG_INF) = 1 would poison the accumulator
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur))
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_cur
        v = v_ref[...].astype(jnp.float32)                  # [page, d]
        if quantized:
            v = v * vs_ref[0, h]
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_s[...]
        # dead rows never ran a page: l == 0 -> exact zeros out
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_s[...] / l_safe).astype(o_ref.dtype)


def _paged_call(qg, k_pool, v_pool, tables, table_index, positions, horizons,
                *, group, scale, sliding_window, interpret):
    """``qg`` [b, nkv, rows, d] kv-head-major query rows -> same shape."""
    quantized = kv_quant.is_quantized(k_pool)
    k_arr, v_arr = (k_pool.q, v_pool.q) if quantized else (k_pool, v_pool)
    b, nkv, rows, d = qg.shape
    num_pages, page_size, _, _ = k_arr.shape

    def page_map(i, h, j, tbl, idx, pos, hor):
        return (tbl[idx[i], j], 0, h)

    def row_map(i, h, j, tbl, idx, pos, hor):
        return (i, h, 0, 0)

    page_spec = pl.BlockSpec((None, page_size, d), page_map)
    row_spec = pl.BlockSpec((None, None, rows, d), row_map)
    in_specs = [row_spec, page_spec, page_spec]
    operands = [qg,
                k_arr.reshape(num_pages, page_size, nkv * d),
                v_arr.reshape(num_pages, page_size, nkv * d)]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, 1, nkv),
            lambda i, h, j, tbl, idx, pos, hor: (tbl[idx[i], j], 0, 0),
            memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [k_pool.scale.reshape(num_pages, 1, nkv),
                     v_pool.scale.reshape(num_pages, 1, nkv)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nkv, tables.shape[1]),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size, group=group,
        sliding_window=sliding_window, quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
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
