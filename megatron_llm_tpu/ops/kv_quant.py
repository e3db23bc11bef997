"""Quantized paged KV-cache storage: int8/fp8 pages with per-page scales.

Decode serving is pool-capacity-bound before it is FLOP-bound: every
concurrency, prefix-cache and speculative-depth limit in the engine traces
back to bf16 KV bytes per page (generation/engine.py), and the draft cache
(ISSUE 9) doubled the pressure.  This module stores the paged pool in int8
(or fp8 e4m3) with **per-page, per-KV-head symmetric absmax scales** — the
page is the pool's unit of allocation, sharing and eviction, so it is also
the right unit of quantization: a page that moves through the prefix trie,
a COW clone or a preemption park carries exactly one scale row with it.

Layout (:class:`QuantPagedKV`, a pytree NamedTuple):

* ``q``     — ``[..., num_pages, page_size, nkv, d]`` int8 / float8_e4m3fn
* ``scale`` — ``[..., num_pages, nkv]`` float32, ``x ~= q * scale``

Both leaves carry the same leading dims as the bf16 pool (the stacked
layer axis included), so ``lax.scan`` over layers, ``jax.tree.map`` page
copies and buffer donation all work unchanged.

Write path (:func:`paged_write`): the engine's three write shapes — the
decode/ragged tick (R single-token rows), chunked prefill (whole chunks
through the block table) and the spec draft scan — all reduce to "R rows,
each one token at ``(page_ids[r], offs[r])``".  Quantized writes must be
page-granular *and* collision-safe (consecutive rows of one chunk or one
verify block land in the SAME page), so the update runs in three phases
whose scatters are each well-defined under duplicate page ids:

1. **scale update** — a page receiving an ``offs == 0`` write is FRESH
   (its first token; any prior content is a previous tenant's garbage):
   its scale resets to this tick's contribution.  Otherwise the scale is
   ``max(old, incoming)`` — per-page absmax never shrinks while the page
   is live.  Both are scatter-``max`` reductions: duplicates compose.
2. **page requantize** — surviving content of written pages is re-rounded
   under the (possibly grown) scale: ``q' = round(q * old/new)``.  The
   rescale depends only on (old page content, old scale, new scale), so
   every duplicate gathered copy computes IDENTICAL bytes and the
   scatter-back is deterministic.  Unchanged scales round-trip exactly
   (``round(q * 1.0) == q``); fresh pages zero (``ratio == 0``).
3. **token write** — each row's value quantized under the new scale at
   its own ``(page, offset)``.  Live rows write disjoint slots by the
   engine's write-then-attend construction; only the reserved null page
   sees duplicates, and its content is garbage by design.

Error bound (tests/test_kv_quant.py): a single whole-page quantization is
the classic symmetric-absmax bound ``|x - q*s| <= s/2`` (``s =
absmax/QMAX``).  A decode append that GROWS the page scale re-rounds
prior tokens once more, each growth adding ``<= s_new/2`` — the exact
analytic bound for a token is ``s_at_write/2 + sum(s_g/2)`` over the
scale growths after it (whole-page writes — prefill chunks — see none of
this: they quantize in one shot).  In practice the re-rounding errors
random-walk rather than add, and measured append error stays under
``2 * s_final/2`` — the single-growth figure :func:`kv_error_bound`
reports as the rule of thumb.

Read path: the jnp fallbacks dequantize at the page gather
(:func:`dequant_gather`); the Pallas kernels take the scale as an extra
blockspec'd operand so the int8->f32 cast and the scale multiply fuse into
the page DMA — HBM traffic stays int8 (ops/pallas/paged_attention.py).

``kv_dtype="bf16"`` never touches this module: the engine keeps plain
arrays and every existing bitwise-parity suite holds byte for byte.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from megatron_llm_tpu.ops.fp8 import E4M3

KV_DTYPES = ("bf16", "int8", "fp8")

# symmetric quantization ranges: int8 uses +-127 (round + clip), fp8 the
# forward format ops/fp8.py already standardizes on (e4m3fn; its
# saturation value — cast after clip, e4m3fn has no inf to absorb
# overflow, a clipped cast keeps garbage finite)
_QMAX = {"int8": 127.0, "fp8": float(jnp.finfo(E4M3).max)}
_QDTYPE = {"int8": jnp.int8, "fp8": E4M3}

# scale floor for divisions only (stored scales keep their true value —
# an all-zero page dequantizes to exact zeros)
_EPS = 1e-20


class QuantPagedKV(NamedTuple):
    """One quantized paged cache: values + per-page, per-head scales."""

    q: jax.Array       # [..., num_pages, page_size, nkv, d] int8/fp8
    scale: jax.Array   # [..., num_pages, nkv] float32


PagedKV = Union[jax.Array, QuantPagedKV]


def is_quantized(pool: PagedKV) -> bool:
    return isinstance(pool, QuantPagedKV)


def qmax_for(kv_dtype: str) -> float:
    return _QMAX[kv_dtype]


def storage_dtype(kv_dtype: str):
    return _QDTYPE[kv_dtype]


def make_pool(shape, kv_dtype: str, compute_dtype) -> PagedKV:
    """Zero-initialized pool of ``shape`` = [..., P, page, nkv, d]:
    a plain ``compute_dtype`` array for ``bf16``, a
    :class:`QuantPagedKV` otherwise."""
    assert kv_dtype in KV_DTYPES, f"kv_dtype must be one of {KV_DTYPES}"
    if kv_dtype == "bf16":
        return jnp.zeros(shape, compute_dtype)
    return QuantPagedKV(
        q=jnp.zeros(shape, _QDTYPE[kv_dtype]),
        scale=jnp.zeros(shape[:-3] + (shape[-2],), jnp.float32),
    )


def page_size_of(pool: PagedKV) -> int:
    arr = pool.q if is_quantized(pool) else pool
    return arr.shape[-3]


def pool_nbytes(pool: PagedKV) -> int:
    """Device bytes of the pool's KV storage (scales counted separately
    by :func:`scale_nbytes` — the capacity bench and /metrics report the
    split so the per-page overhead stays visible)."""
    arr = pool.q if is_quantized(pool) else pool
    return arr.size * arr.dtype.itemsize


def scale_nbytes(pool: PagedKV) -> int:
    return pool.scale.size * pool.scale.dtype.itemsize if is_quantized(
        pool) else 0


def _qmax_of(pool: QuantPagedKV) -> float:
    return _QMAX["int8"] if pool.q.dtype == jnp.int8 else _QMAX["fp8"]


def _cast_q(x32: jax.Array, qdtype) -> jax.Array:
    """fp32 -> storage rounding: round+clip for int8, clipped RNE cast
    for fp8 (saturation keeps even garbage pages finite)."""
    if qdtype == jnp.int8:
        return jnp.clip(jnp.round(x32), -127.0, 127.0).astype(jnp.int8)
    return jnp.clip(x32, -_QMAX["fp8"], _QMAX["fp8"]).astype(qdtype)


def quantize_pages(vals: jax.Array, kv_dtype: str) -> QuantPagedKV:
    """Whole-page quantization of ``vals`` [..., page, nkv, d]: the
    single-shot form the error bound is stated against."""
    qmax = _QMAX[kv_dtype]
    v32 = vals.astype(jnp.float32)
    scale = jnp.max(jnp.abs(v32), axis=(-3, -1)) / qmax  # [..., nkv]
    den = jnp.maximum(scale, _EPS)
    q = _cast_q(v32 / den[..., None, :, None], _QDTYPE[kv_dtype])
    return QuantPagedKV(q=q, scale=scale)


def dequantize_pages(pages: QuantPagedKV, dtype) -> jax.Array:
    """[..., page, nkv, d] values back in ``dtype``."""
    return (pages.q.astype(jnp.float32)
            * pages.scale[..., None, :, None]).astype(dtype)


def kv_error_bound(vals: jax.Array, kv_dtype: str,
                   appends: bool = False) -> float:
    """Max absolute dequantization error for page content ``vals``
    [..., page, nkv, d]: ``scale/2`` per (page, head) for a single-shot
    page quantization; ``appends`` doubles it — the single-growth figure
    (one extra re-rounding under the final scale), the empirical rule of
    thumb for decode-append pages (module docstring; the exact
    multi-growth bound is the per-growth sum tracked in
    tests/test_kv_quant.py::test_append_requant_error_bound)."""
    qmax = _QMAX[kv_dtype]
    scale = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=(-3, -1)) / qmax
    bound = float(jnp.max(scale)) / 2.0
    return 2.0 * bound if appends else bound


# ---------------------------------------------------------------------------
# The write path
# ---------------------------------------------------------------------------


def paged_write(pool: PagedKV, page_ids: jax.Array, offs: jax.Array,
                vals: jax.Array) -> PagedKV:
    """Write ``vals[b, s, nkv, d]`` at ``(page_ids[b, s], offs[b, s])``.

    Plain pools keep the engine's original scatter expression byte for
    byte (the ``--kv_dtype bf16`` bitwise contract).  Quantized pools run
    the three-phase page-granular update from the module docstring."""
    if not is_quantized(pool):
        return pool.at[page_ids, offs].set(vals.astype(pool.dtype))
    b, s = page_ids.shape
    return _quant_write_rows(
        pool, page_ids.reshape(b * s), offs.reshape(b * s),
        vals.reshape(b * s, *vals.shape[2:]))


def _quant_write_rows(pool: QuantPagedKV, page_ids: jax.Array,
                      offs: jax.Array, vals: jax.Array) -> QuantPagedKV:
    """R rows, one token each; collision-safe (see module docstring)."""
    qdtype = pool.q.dtype
    qmax = _qmax_of(pool)
    num_pages = pool.q.shape[0]
    v32 = vals.astype(jnp.float32)                        # [R, nkv, d]
    s_row = jnp.max(jnp.abs(v32), axis=-1) / qmax         # [R, nkv]

    # 1) scale update.  offs == 0 marks the page's FIRST token: everything
    # in it is a previous tenant's garbage, so the old scale (and content)
    # must not leak into the new tenant's quantization.
    fresh_rows = (offs == 0).astype(jnp.int32)
    fresh = jnp.zeros((num_pages,), jnp.int32).at[page_ids].max(fresh_rows)
    old_scale = pool.scale                                 # [P, nkv]
    kept_scale = jnp.where(fresh[:, None] > 0, 0.0, old_scale)
    new_scale = kept_scale.at[page_ids].max(s_row)         # [P, nkv]
    den = jnp.maximum(new_scale, _EPS)

    # 2) requantize surviving content of the written pages.  ``ratio``
    # is per PAGE, so duplicate gathered copies rescale identically and
    # the scatter-back is deterministic; fresh pages zero out (ratio 0),
    # untouched positions under an unchanged scale round-trip exactly.
    ratio = (kept_scale / den)[page_ids]                   # [R, nkv]
    gathered = pool.q[page_ids].astype(jnp.float32)        # [R, page, nkv, d]
    requant = _cast_q(gathered * ratio[:, None, :, None], qdtype)
    q = pool.q.at[page_ids].set(requant)

    # 3) the tokens themselves, under the new scale
    tok_q = _cast_q(v32 / den[page_ids][..., None], qdtype)
    q = q.at[page_ids, offs].set(tok_q)
    return QuantPagedKV(q=q, scale=new_scale)


# ---------------------------------------------------------------------------
# The read path (jnp fallbacks; the Pallas kernels dequant in-kernel)
# ---------------------------------------------------------------------------


def dequant_gather(pool: PagedKV, block_tables: jax.Array,
                   dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """[T, W*page, nkv, d] dense view of the block-tabled pages.

    Plain pools return the engine's original gather untouched (bitwise);
    quantized pools dequantize at the gather — ``dtype`` (the query/compute
    dtype) is the dequant target."""
    T = block_tables.shape[0]
    if not is_quantized(pool):
        nkv, d = pool.shape[-2], pool.shape[-1]
        return pool[block_tables].reshape(T, -1, nkv, d)
    nkv, d = pool.q.shape[-2], pool.q.shape[-1]
    dt = dtype if dtype is not None else jnp.float32
    g = pool.q[block_tables].astype(jnp.float32)   # [T, W, page, nkv, d]
    s = pool.scale[block_tables]                   # [T, W, nkv]
    return (g * s[..., None, :, None]).astype(dt).reshape(T, -1, nkv, d)
