"""Learned sparse attention over the paged latent pool (the DeepSeek-V3.2
lightning indexer; models/sparse_mla.py is the sublayer).  A query row
scores EVERY index key of its sequence (``I[t, s] = sum_j w_j ReLU(q_j .
k_s)``, one float32 number a key, read through the block table from the
index keys' own leaf: 256 bytes a key at 128 bf16 lanes, not the latent
row's 1,280), picks the ``k`` best EXACTLY (ties to the earlier position;
every key where it has at most ``k``), and attends the picked latent rows
alone in the absorbed form.

:func:`sparse_attention` runs a tick's rows a TILE of ``TILE`` rows at a
time and takes, a tile, one of three paths that the rows' own data pick:

* **dead** — no row of the tile is live (idle slots, a chunk's padding):
  nothing is read;
* **shared** — the tile's rows all name ONE block table (a prompt chunk's
  rows): a block of its index keys is gathered once and multiplied with all
  the rows' queries; the selection stays a MASK; the latent rows are read
  once a block and every row attends them under its own mask, softmax
  carried across blocks.  Nothing is compacted and no row is gathered;
* **lone** — rows of tables of their own (decode rows): each row gathers
  its own keys, its mask is compacted to a list of ``k`` positions (two
  levels of counts, no sort, no scatter), the picked latent rows are
  gathered one by one (a picked token's page holds fifteen that were not
  picked) and attended in the dense form over ``[rows, k]``.

The selection is a threshold, not a sort: the k-th largest score of a row
is found bit by bit over the scores' order-preserving integer image (32
counting passes), then ``>`` it plus the earliest ties.  ``lax.top_k``
gives the same sets and cost the same time on the chip with the list made
(PERF.md section 6, PR 67); the shared path needs the mask and no list.

Scopes a device trace can price: ``index_score`` (the sweep),
``index_select`` (threshold and compaction), ``sparse_gather`` (the picked
rows' gather, and the shared path's reading of its latent blocks),
``sparse_attention``.  Everything is XLA: kernels that walk the block table
and a token list are a later step (PERF.md).  Scores, selection and softmax
are float32 on the operands' own dtype, the precision of every other
attention here.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# rows a tile: a prompt chunk is whole pages, so its tiles share a table
TILE = 32
# keys a step of the sweep scores: 128 pages of 16
SWEEP_KEYS = 2048
# the selection's counting block (a lane row)
_BLOCK = 128


def flat_rows(leaf: jax.Array, layer) -> tuple:
    """``(flat, base)``: a layered leaf ``[L, P, page, w]`` as the ``[L * P
    * page, w]`` rows every layer's tokens live in (merging leading dims
    moves no byte) and the first row of ``layer`` in it."""
    n_layers, n_pages, page, w = leaf.shape
    return leaf.reshape(n_layers * n_pages * page, w), layer * n_pages * page


def write_rows(leaf: jax.Array, layer, page_ids: jax.Array, offs: jax.Array,
               rows: jax.Array) -> jax.Array:
    """Scatter ``rows`` [R, w] to ``(page_ids, offs)`` [R] of ``layer`` in
    place (the index keys' write; the latent row's is kv_quant's)."""
    flat, base = flat_rows(leaf, layer)
    page = leaf.shape[2]
    flat = flat.at[base + page_ids * page + offs].set(rows.astype(leaf.dtype))
    return flat.reshape(leaf.shape)


class _Blocks(NamedTuple):
    """How a table of ``width`` pages is walked: ``pages`` a step."""

    pages: int
    steps: int

    @classmethod
    def of(cls, width: int, page: int) -> "_Blocks":
        pages = max(1, min(SWEEP_KEYS // page, width))
        return cls(pages, -(-width // pages))


def _page_block(leaf: jax.Array, layer, tables: jax.Array, i, blocks):
    """Block ``i`` of the pages ``tables`` [g, steps * pages] name in
    ``layer``: ``[g, pages * page, w]``."""
    n_layers, n_pages, page, w = leaf.shape
    ids = jax.lax.dynamic_slice_in_dim(tables, i * blocks.pages, blocks.pages,
                                       axis=1)
    flat = leaf.reshape(n_layers * n_pages, page, w)
    return flat[layer * n_pages + ids].reshape(
        tables.shape[0], blocks.pages * page, w)


def _padded(tables: jax.Array, blocks: _Blocks) -> jax.Array:
    return jnp.pad(tables, ((0, 0),
                            (0, blocks.steps * blocks.pages - tables.shape[1])))


@jax.named_scope("index_score")
def index_scores(q: jax.Array, w: jax.Array, index_leaf: jax.Array, layer,
                 tables: jax.Array, ctx: jax.Array) -> jax.Array:
    """``I[r, s] = sum_j w[r, j] ReLU(q[r, j] . k_s)`` for the keys ``s <
    ctx[r]`` of row ``r``, ``-inf`` past them: ``[R, steps * keys a step]``
    float32.  ``q`` [R, H, D], ``w`` [R, H] float32, ``ctx`` [R] (0: a dead
    row); ``tables`` [R, W] page ids a row, or [1, W]: ONE table that every
    row reads (its keys are then gathered once a step).  As many steps run
    as the longest context needs."""
    page = index_leaf.shape[2]
    rows = q.shape[0]
    blocks = _Blocks.of(tables.shape[1], page)
    keys = blocks.pages * page
    tables = _padded(tables, blocks)
    wf = w.astype(jnp.float32)
    one = tables.shape[0] == 1

    def step(i, scores):
        k = _page_block(index_leaf, layer, tables, i, blocks)
        s = jnp.einsum("rhd,kd->rhk" if one else "rhd,rkd->rhk", q,
                       k[0] if one else k, preferred_element_type=jnp.float32)
        s = jnp.einsum("rh,rhk->rk", wf, jnp.maximum(s, 0.0))
        return jax.lax.dynamic_update_slice_in_dim(scores, s, i * keys,
                                                   axis=1)

    scores = jax.lax.fori_loop(
        0, -(-jnp.max(ctx) // keys), step,
        jnp.full((rows, blocks.steps * keys), -jnp.inf, jnp.float32))
    s_pos = jnp.arange(scores.shape[1], dtype=jnp.int32)
    # + 0.0: a sum of -0.0 terms is +0.0 to the threshold's integer image
    return jnp.where(s_pos[None, :] < ctx[:, None], scores + 0.0, -jnp.inf)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32, order-preserving (no NaN is made here)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _block_cumsum(mask: jax.Array):
    """``mask`` [R, nb, 128] bool -> (inclusive count within a block [R, nb,
    128], blocks' exclusive running count [R, nb], total [R]) int32; the
    within-block sums ride one triangular matmul (exact: counts <= 128)."""
    tri = jnp.triu(jnp.ones((_BLOCK, _BLOCK), jnp.bfloat16))
    inside = jnp.einsum("rbs,st->rbt", mask.astype(jnp.bfloat16), tri,
                        preferred_element_type=jnp.float32).astype(jnp.int32)
    upto = jnp.cumsum(inside[..., -1], axis=-1)
    return inside, upto - inside[..., -1], upto[..., -1]


@jax.named_scope("index_select")
def select_mask(scores: jax.Array, ctx: jax.Array, k: int) -> jax.Array:
    """The mask ``[R, S]`` of the ``k`` largest ``scores`` of every row,
    ties to the earlier position; every key ``s < ctx`` where a row has at
    most ``k``.  ``scores`` [R, S] float32, ``-inf`` at and past ``ctx``
    (:func:`index_scores`)."""
    rows, s = scores.shape
    if s <= k:
        return jnp.arange(s, dtype=jnp.int32)[None, :] < ctx[:, None]
    pad = -s % _BLOCK
    u = _sortable(jnp.pad(scores, ((0, 0), (0, pad)),
                          constant_values=-jnp.inf))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (u >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, t)

    # the k-th largest value of a row: the largest t with k values >= t
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32))
    above = u > kth[:, None]
    tie = (u == kth[:, None]).reshape(rows, -1, _BLOCK)
    inside, before, _ = _block_cumsum(tie)
    tie_rank = (inside + before[..., None]).reshape(rows, -1)
    need = k - above.sum(-1)
    sel = above | (tie.reshape(rows, -1) & (tie_rank <= need[:, None]))
    live = jnp.arange(s + pad, dtype=jnp.int32)[None, :] < ctx[:, None]
    return (sel & live)[:, :s]


@jax.named_scope("index_select")
def compact(sel: jax.Array, k: int):
    """The positions of the set bits of ``sel`` [R, S] (at most ``k`` a
    row), ascending: ``(idx [R, k] int32, valid [R, k])``, no sort and no
    scatter.  Slot ``j`` finds its block by counting the blocks that end
    at or before it, gathers that block's 128 running counts, and counts
    again."""
    rows, s = sel.shape
    k = min(k, s)
    sel = jnp.pad(sel, ((0, 0), (0, -s % _BLOCK)))
    nb = sel.shape[1] // _BLOCK
    inside, before, total = _block_cumsum(sel.reshape(rows, nb, _BLOCK))
    upto = inside + before[..., None]        # a key's rank among the picked
    j = jnp.arange(k, dtype=jnp.int32)
    blk = (upto[..., -1][:, None, :] <= j[None, :, None]).sum(-1)   # [R, k]
    blk = jnp.minimum(blk, nb - 1)
    ranks = jnp.take_along_axis(upto, blk[..., None], axis=1)    # [R, k, 128]
    off = (ranks <= j[None, :, None]).sum(-1)
    valid = j[None, :] < total[:, None]
    idx = jnp.where(valid, blk * _BLOCK + jnp.minimum(off, _BLOCK - 1), 0)
    return idx.astype(jnp.int32), valid


def _softmax_rows(s: jax.Array) -> jax.Array:
    """softmax over the last axis; a row of ``-inf`` alone gives zeros."""
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    return e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)


def attend_list(q_abs: jax.Array, latent_leaf: jax.Array, layer,
                tables: jax.Array, idx: jax.Array, valid: jax.Array,
                scale: float, value_width: int) -> jax.Array:
    """Every row over its own list of picked tokens: ``q_abs`` [R, n,
    width] (the pool row's padded width), ``tables`` [R, W], ``idx`` /
    ``valid`` [R, k] (:func:`compact`); returns ``[R, n, value_width]``
    float32, zeros for a row that picked nothing."""
    page = latent_leaf.shape[2]
    flat, base = flat_rows(latent_leaf, layer)
    pages = jnp.take_along_axis(tables, idx // page, axis=1)
    with jax.named_scope("sparse_gather"):
        rows = flat[base + pages * page + idx % page]            # [R, k, w]
    with jax.named_scope("sparse_attention"):
        s = jnp.einsum("rnd,rkd->rnk", q_abs, rows,
                       preferred_element_type=jnp.float32) * scale
        p = _softmax_rows(jnp.where(valid[:, None, :], s, -jnp.inf))
        return jnp.einsum("rnk,rkd->rnd", p.astype(q_abs.dtype),
                          rows[..., :value_width],
                          preferred_element_type=jnp.float32)


def attend_masked(q_abs: jax.Array, latent_leaf: jax.Array, layer,
                  table: jax.Array, sel: jax.Array, ctx: jax.Array,
                  scale: float, value_width: int) -> jax.Array:
    """Every row over the keys its mask ``sel`` [R, S] admits of ONE
    ``table`` [1, W] they all read: a block of latent rows is read once
    and the softmax is carried across blocks.  ``[R, n, value_width]``
    float32."""
    page = latent_leaf.shape[2]
    rows, n, _ = q_abs.shape
    blocks = _Blocks.of(table.shape[1], page)
    keys = blocks.pages * page
    table = _padded(table, blocks)
    sel = jnp.pad(sel, ((0, 0), (0, blocks.steps * keys - sel.shape[1])))

    def step(i, carry):
        top, norm, acc = carry
        with jax.named_scope("sparse_gather"):
            lat = _page_block(latent_leaf, layer, table, i, blocks)[0]
        with jax.named_scope("sparse_attention"):
            s = jnp.einsum("rnd,kd->rnk", q_abs, lat,
                           preferred_element_type=jnp.float32) * scale
            ok = jax.lax.dynamic_slice_in_dim(sel, i * keys, keys, axis=1)
            s = jnp.where(ok[:, None, :], s, -jnp.inf)
            new = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
            safe = jnp.where(jnp.isfinite(new), new, 0.0)
            p, keep = jnp.exp(s - safe), jnp.exp(top - safe)
            acc = acc * keep + jnp.einsum(
                "rnk,kd->rnd", p.astype(q_abs.dtype), lat[:, :value_width],
                preferred_element_type=jnp.float32)
            return new, norm * keep + p.sum(-1, keepdims=True), acc

    start = (jnp.full((rows, n, 1), -jnp.inf, jnp.float32),
             jnp.zeros((rows, n, 1), jnp.float32),
             jnp.zeros((rows, n, value_width), jnp.float32))
    _, norm, acc = jax.lax.fori_loop(0, -(-jnp.max(ctx) // keys), step, start)
    return acc / jnp.maximum(norm, 1e-30)


def sparse_attention(q_index: jax.Array, w_index: jax.Array,
                     q_abs: jax.Array, index_leaf: jax.Array,
                     latent_leaf: jax.Array, layer, tables: jax.Array,
                     index: jax.Array, ctx: jax.Array, topk: int,
                     scale: float, value_width: int) -> jax.Array:
    """Sweep, select and attend for the rows of a tick, a tile at a time
    (the module's docstring has the three paths).  ``q_index`` [R, H, D],
    ``w_index`` [R, H], ``q_abs`` [R, n, width]; ``tables`` [T, W] the
    tick's block tables and ``index`` [R] each row's; ``ctx`` [R] the keys
    a row may see (0: dead).  Returns ``[R, n, value_width]`` in
    ``q_abs``'s dtype."""
    rows, n, _ = q_abs.shape
    args = (q_index, w_index, q_abs, index, ctx)

    def picked(q_i, w_i, ctx_t, read):      # the rows' masks, off ``read``
        return select_mask(
            index_scores(q_i, w_i, index_leaf, layer, read, ctx_t), ctx_t,
            topk)

    def lone(q_i, w_i, q, idx_t, ctx_t):
        own = tables[idx_t]
        return attend_list(
            q, latent_leaf, layer, own,
            *compact(picked(q_i, w_i, ctx_t, own), topk), scale, value_width)

    def shared(q_i, w_i, q, idx_t, ctx_t):
        table = tables[idx_t[:1]]
        return attend_masked(
            q, latent_leaf, layer, table, picked(q_i, w_i, ctx_t, table),
            ctx_t, scale, value_width)

    def dead(q_i, w_i, q, idx_t, ctx_t):
        return jnp.zeros((q.shape[0], n, value_width), jnp.float32)

    def tile(a):
        idx_t, ctx_t = a[3], a[4]
        mode = jnp.where(jnp.max(ctx_t) <= 0, 0,
                         jnp.where(jnp.all(idx_t == idx_t[0]), 1, 2))
        return jax.lax.switch(mode, (dead, shared, lone), *a)

    if rows <= TILE:
        return tile(args).astype(q_abs.dtype)
    pad = -rows % TILE
    tiles = jax.tree.map(
        lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, TILE) + a.shape[1:]), args)
    out = jax.lax.map(tile, tiles)
    return out.reshape((-1,) + out.shape[2:])[:rows].astype(q_abs.dtype)
