"""Quantized paged KV-cache storage: int8/fp8 pages with per-page scales.

Decode serving is pool-capacity-bound before it is FLOP-bound: every
concurrency, prefix-cache and speculative-depth limit in the engine traces
back to bf16 KV bytes per page (generation/engine.py), and the draft cache
(ISSUE 9) doubled the pressure.  This module stores the paged pool in int8
(or fp8 e4m3) with **per-page, per-KV-head symmetric absmax scales** — the
page is the pool's unit of allocation, sharing and eviction, so it is also
the right unit of quantization: a page that moves through the prefix trie,
a COW clone or a preemption park carries exactly one scale row with it.

**This module owns the pool's physical row** (ISSUE 32).  A paged pool
is ONE leaf ``[layers, num_pages, page_size, H * d]``: a token's row is
``H`` *storage heads* of ``d`` values, flattened, so the row the kernel
copies out of HBM is the row the write scattered — no pad, no transpose,
no per-layer slice in between.  A K/V pool has ``H = 2 * nkv``: a head's
key and value side by side, ``[page, nkv, (k|v), d]`` (:func:`pack_kv`,
:func:`split_kv`), which is whole 128-lane rows whenever ``2 * d`` is
(Falcon-7B's one head of 64: exactly 128 lanes; Mistral's 8 x 128: 2048)
and shards over ``tp`` by whole heads.  A latent pool (MLA) has ``H = 1``
and ``d`` = its padded width.  Everything off the tick's hot path speaks
the logical ``(page, offset, head, d)`` view through :func:`heads_view`,
:func:`split_kv`, :func:`dequant_gather`; the wire format of the handoff
keeps its K and V leaves (:func:`split_kv` / :func:`pack_kv` on the host).

The pool rides the layer scan's CARRY (models/transformer.py): a layer
writes and reads its own pages of the flat ``[layers * num_pages, ...]``
view at ``page_ids + layer * num_pages`` (:func:`paged_write`,
:func:`layer_view`), in place.

Layout of a quantized pool (:class:`QuantPagedKV`, a pytree NamedTuple):

* ``q``     — ``[..., num_pages, page_size, H * d]`` int8 / float8_e4m3fn
* ``scale`` — ``[..., num_pages, H]`` float32, ``x ~= q * scale``

Both leaves carry the same leading dims as the bf16 pool, so
``jax.tree.map`` page copies and buffer donation work unchanged; a key
and a value head are two storage heads with a scale each.

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
import numpy as np

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

    q: jax.Array       # [..., num_pages, page_size, H * d] int8/fp8
    scale: jax.Array   # [..., num_pages, H] float32


class IndexedLatent(NamedTuple):
    """A latent pool whose tokens also keep an INDEX KEY (learned sparse
    attention, ops/sparse_attention.py): two leaves under one page id, so
    that a sweep over a sequence's index keys reads their rows alone."""

    rows: jax.Array    # [L, num_pages, page_size, latent lanes]
    index: jax.Array   # [L, num_pages, page_size, index_head_dim]


PagedKV = Union[jax.Array, QuantPagedKV, IndexedLatent]


def is_quantized(pool: PagedKV) -> bool:
    return isinstance(pool, QuantPagedKV)


def qmax_for(kv_dtype: str) -> float:
    return _QMAX[kv_dtype]


def storage_dtype(kv_dtype: str):
    return _QDTYPE[kv_dtype]


def make_pool(shape, kv_dtype: str, compute_dtype) -> PagedKV:
    """Zero-initialized pool of LOGICAL ``shape`` = [..., P, page, H, d],
    stored ``[..., P, page, H * d]``: a plain ``compute_dtype`` array for
    ``bf16``, a :class:`QuantPagedKV` otherwise."""
    assert kv_dtype in KV_DTYPES, f"kv_dtype must be one of {KV_DTYPES}"
    *lead, heads, d = shape
    stored = tuple(lead) + (heads * d,)
    if kv_dtype == "bf16":
        return jnp.zeros(stored, compute_dtype)
    return QuantPagedKV(
        q=jnp.zeros(stored, _QDTYPE[kv_dtype]),
        scale=jnp.zeros(tuple(lead[:-1]) + (heads,), jnp.float32),
    )


def make_kv_pool(layers: int, num_pages: int, page_size: int, nkv: int,
                 d: int, kv_dtype: str, compute_dtype) -> PagedKV:
    """The K/V pool of ``layers`` layers: ``2 * nkv`` storage heads, a
    head's key and value side by side (:func:`pack_kv`)."""
    return make_pool((layers, num_pages, page_size, 2 * nkv, d), kv_dtype,
                     compute_dtype)


def values_of(pool: PagedKV) -> jax.Array:
    """The value leaf (``q`` of a quantized pool, the latent rows of an
    indexed one)."""
    if isinstance(pool, IndexedLatent):
        return pool.rows
    return pool.q if is_quantized(pool) else pool


def page_size_of(pool: PagedKV) -> int:
    return values_of(pool).shape[-2]


def row_width(pool: PagedKV) -> int:
    """Values of one token's row: ``H * d``."""
    return values_of(pool).shape[-1]


def heads_view(rows, d: int):
    """``[..., H * d]`` stored rows as logical ``[..., H, d]`` (numpy or
    jax).  On a gathered page set, never on a whole pool inside a tick:
    splitting the lane dim of a tiled array is a copy."""
    return rows.reshape(*rows.shape[:-1], rows.shape[-1] // d, d)


def pack_kv(k, v):
    """Keys and values ``[..., nkv, d]`` as storage heads ``[..., 2 * nkv,
    d]``: head ``h``'s key is storage head ``2h``, its value ``2h + 1``.
    numpy in, numpy out."""
    xp = np if isinstance(k, np.ndarray) else jnp
    return xp.stack([k, v], axis=-2).reshape(
        *k.shape[:-2], 2 * k.shape[-2], k.shape[-1])


def split_kv(heads):
    """Storage heads ``[..., 2 * nkv, d]`` -> (keys, values), each
    ``[..., nkv, d]``; the inverse of :func:`pack_kv`."""
    return heads[..., 0::2, :], heads[..., 1::2, :]


def pack_kv_scales(k_scale, v_scale):
    """Per-head scales ``[..., nkv]`` x 2 -> ``[..., 2 * nkv]`` in storage
    head order."""
    xp = np if isinstance(k_scale, np.ndarray) else jnp
    return xp.stack([k_scale, v_scale], axis=-1).reshape(
        *k_scale.shape[:-1], 2 * k_scale.shape[-1])


def kv_to_leaves(pages: PagedKV, d: int, prefix: str = "") -> dict:
    """Host pages of a K/V pool ``[..., page, 2*nkv*d]`` as the handoff's
    LOGICAL wire leaves: ``k`` and ``v`` ``[..., page, nkv, d]``, for a
    quantized pool ``k.q``, ``k.scale`` ``[..., nkv]``, ``v.q``,
    ``v.scale``.  The wire never sees the physical row."""
    quant = is_quantized(pages)
    k, v = split_kv(heads_view(np.asarray(values_of(pages)), d))
    sfx = ".q" if quant else ""
    out = {prefix + "k" + sfx: np.ascontiguousarray(k),
           prefix + "v" + sfx: np.ascontiguousarray(v)}
    if quant:
        scale = np.asarray(pages.scale)
        out[prefix + "k.scale"] = np.ascontiguousarray(scale[..., 0::2])
        out[prefix + "v.scale"] = np.ascontiguousarray(scale[..., 1::2])
    return out


def kv_from_leaves(leaves: dict, quantized: bool, prefix: str = ""):
    """The inverse of :func:`kv_to_leaves`: stored rows (and scales) of
    the pages the leaves hold, as host arrays, bytes verbatim."""
    sfx = ".q" if quantized else ""
    heads = pack_kv(np.asarray(leaves[prefix + "k" + sfx]),
                    np.asarray(leaves[prefix + "v" + sfx]))
    rows = heads.reshape(*heads.shape[:-2], -1)
    if not quantized:
        return rows
    return QuantPagedKV(q=rows, scale=pack_kv_scales(
        np.asarray(leaves[prefix + "k.scale"]),
        np.asarray(leaves[prefix + "v.scale"])))


def layer_view(pool: PagedKV, layer):
    """``(flat, base)`` for a layered pool ``[L, P, page, row]``: the
    ``[L * P, page, row]`` view every layer's pages live in (merging the
    leading dims moves no byte) and the first page of ``layer`` in it.  A
    quantized pool's ``scale`` comes back as that layer's ``[P, H]`` slice
    (small: read per layer by the kernel's scale rows and the requantizing
    write).  ``layer=None``: the pool is one layer's already, base 0."""
    if layer is None:
        return pool, 0
    arr = values_of(pool)
    n_layers, n_pages = arr.shape[:2]
    flat = arr.reshape((n_layers * n_pages,) + arr.shape[2:])
    if is_quantized(pool):
        flat = QuantPagedKV(q=flat, scale=pool.scale[layer])
    return flat, layer * n_pages


def pool_nbytes(pool: PagedKV) -> int:
    """Device bytes of the pool's KV storage (scales counted separately
    by :func:`scale_nbytes` — the capacity bench and /metrics report the
    split so the per-page overhead stays visible)."""
    arr = values_of(pool)
    more = pool.index.size * pool.index.dtype.itemsize if isinstance(
        pool, IndexedLatent) else 0
    return arr.size * arr.dtype.itemsize + more


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
    """Whole-page quantization of logical ``vals`` [..., page, H, d] into
    stored rows: the single-shot form the error bound is stated against."""
    qmax = _QMAX[kv_dtype]
    v32 = vals.astype(jnp.float32)
    scale = jnp.max(jnp.abs(v32), axis=(-3, -1)) / qmax  # [..., H]
    den = jnp.maximum(scale, _EPS)
    q = _cast_q(v32 / den[..., None, :, None], _QDTYPE[kv_dtype])
    return QuantPagedKV(q=q.reshape(*q.shape[:-2], -1), scale=scale)


def dequantize_pages(pages: QuantPagedKV, dtype) -> jax.Array:
    """Logical [..., page, H, d] values back in ``dtype``."""
    heads = pages.scale.shape[-1]
    q = heads_view(pages.q, pages.q.shape[-1] // heads)
    return (q.astype(jnp.float32)
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
                vals: jax.Array, layer=None) -> PagedKV:
    """Write ``vals[b, s, H, d]`` at ``(page_ids[b, s], offs[b, s])``.

    ``layer`` (a traced index): ``pool`` is the layered ``[L, P, page,
    row]`` pool and the write lands in that layer's pages of the flat view,
    in place when the pool is a donated buffer or a loop's carry; None:
    ``pool`` is one layer's ``[P, page, row]``.

    Plain pools are one scatter of whole rows.  Quantized pools run the
    three-phase page-granular update from the module docstring; their
    scale update works on the layer's ``[P, H]`` slice."""
    b, s = page_ids.shape
    flat, base = layer_view(pool, layer)
    arr = values_of(pool)
    if not is_quantized(pool):
        rows = vals.reshape(b, s, -1).astype(arr.dtype)
        return flat.at[page_ids + base, offs].set(rows).reshape(arr.shape)
    q, scale = _quant_write_rows(
        flat, page_ids.reshape(b * s), offs.reshape(b * s),
        vals.reshape(b * s, *vals.shape[2:]), base)
    if layer is not None:
        scale = pool.scale.at[layer].set(scale)
    return QuantPagedKV(q=q.reshape(arr.shape), scale=scale)


def _quant_write_rows(pool: QuantPagedKV, page_ids: jax.Array,
                      offs: jax.Array, vals: jax.Array, base=0):
    """R rows, one token each; collision-safe (see module docstring).
    ``pool.q`` holds the pages at ``page_ids + base``, ``pool.scale`` is
    ``[P, H]`` indexed by ``page_ids``.  Returns (q, scale)."""
    qdtype = pool.q.dtype
    qmax = _qmax_of(pool)
    num_pages = pool.scale.shape[0]
    n_rows, heads, d = vals.shape
    rows = page_ids + base
    v32 = vals.astype(jnp.float32)                        # [R, H, d]
    s_row = jnp.max(jnp.abs(v32), axis=-1) / qmax         # [R, H]

    # 1) scale update.  offs == 0 marks the page's FIRST token: everything
    # in it is a previous tenant's garbage, so the old scale (and content)
    # must not leak into the new tenant's quantization.
    fresh_rows = (offs == 0).astype(jnp.int32)
    fresh = jnp.zeros((num_pages,), jnp.int32).at[page_ids].max(fresh_rows)
    old_scale = pool.scale                                 # [P, H]
    kept_scale = jnp.where(fresh[:, None] > 0, 0.0, old_scale)
    new_scale = kept_scale.at[page_ids].max(s_row)         # [P, H]
    den = jnp.maximum(new_scale, _EPS)

    # 2) requantize surviving content of the written pages.  ``ratio``
    # is per PAGE, so duplicate gathered copies rescale identically and
    # the scatter-back is deterministic; fresh pages zero out (ratio 0),
    # untouched positions under an unchanged scale round-trip exactly.
    ratio = (kept_scale / den)[page_ids]                   # [R, H]
    gathered = heads_view(pool.q[rows], d).astype(jnp.float32)
    requant = _cast_q(gathered * ratio[:, None, :, None], qdtype)
    q = pool.q.at[rows].set(requant.reshape(n_rows, -1, heads * d))

    # 3) the tokens themselves, under the new scale
    tok_q = _cast_q(v32 / den[page_ids][..., None], qdtype)
    q = q.at[rows, offs].set(tok_q.reshape(n_rows, heads * d))
    return q, new_scale


# ---------------------------------------------------------------------------
# The read path (jnp fallbacks; the Pallas kernels dequant in-kernel)
# ---------------------------------------------------------------------------


def dequant_gather(pool: PagedKV, block_tables: jax.Array, d: int,
                   dtype: Optional[jnp.dtype] = None,
                   layer=None) -> jax.Array:
    """Logical [T, W*page, H, d] dense view of the block-tabled pages (of
    ``layer`` where the pool is layered: :func:`layer_view`).

    Plain pools return the gathered rows untouched (bitwise); quantized
    pools dequantize at the gather — ``dtype`` (the query/compute dtype)
    is the dequant target."""
    T = block_tables.shape[0]
    flat, base = layer_view(pool, layer)
    if not is_quantized(pool):
        return heads_view(flat[block_tables + base], d).reshape(
            T, -1, flat.shape[-1] // d, d)
    dt = dtype if dtype is not None else jnp.float32
    g = heads_view(flat.q[block_tables + base], d).astype(jnp.float32)
    s = flat.scale[block_tables]                   # [T, W, H]
    return (g * s[..., None, :, None]).astype(dt).reshape(
        T, -1, g.shape[-2], d)
