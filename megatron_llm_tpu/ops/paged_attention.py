"""Paged KV-cache decode attention: block-table gather + masked softmax.

The serving engine (generation/engine.py) stores the KV cache as a pool of
fixed-size pages shared by all in-flight sequences, ONE leaf ``[layers,
num_pages, page_size, row]`` whose row ops/kv_quant.py owns (a head's key
and value side by side; a latent row for MLA); each sequence owns an
ordered list of page ids (its *block table*).  Every function here takes
that leaf — one layer's ``[num_pages, page_size, row]``, or the layered
pool with ``layer=`` naming the caller's — and reads it in place.  This
module computes one decode step of attention for a batch of sequences at
heterogeneous positions — the Ragged-Paged-Attention decomposition
(PAPERS.md): a single fused program per tick regardless of the
per-sequence context lengths.

Two implementations with the same fp32-softmax numerics:

* ``ops/pallas/paged_attention.py`` — the TPU kernel: the block table is a
  scalar-prefetch operand and the pool stays in HBM; one program per tile
  of 8 rows walks a context in blocks of several pages, copying the next
  block's pages into VMEM while the current one is scored (no [b, max_seq]
  gather ever materializes; slots past the context are never looked up)
  and the online-softmax accumulator carries across blocks.  The rows of
  a tile that name ONE table (a prompt chunk's, a diffusion block's) share
  ONE walk and one matmul, whole, whatever their positions; rows of
  different sequences that stand in one tile share one over the whole
  compute blocks in which their tables name the same pages (a cached
  prefix); any other row, and what is a row's own, walks alone.
* the jnp path below — gathers the block-tabled pages into a dense
  [b, max_seq] view and reuses :func:`ops.attention.xla_attention`.  It
  matches the dense-cache decode path on the same context (the parity
  contract tier-1 enforces on CPU, tests/test_paged_engine.py) and is the
  reference tools/tpu_kernel_check.py holds the compiled kernel to.

Which one a traced program took is printed once per distinct reason
(:func:`ops.attention.announce_path`); :func:`_kernel_refusal` is the
whole rule.

Page 0 of the pool is reserved as the *null page*: the engine never
allocates it, inactive slots' block tables point at it, and writes routed
there are garbage by design (they are never attended to).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.ops import attention as attn_ops
from megatron_llm_tpu.ops import kv_quant
from megatron_llm_tpu.ops.pallas import paged_attention as pallas_paged


class PagedState(NamedTuple):
    """Per-call addressing state threaded through model_forward.

    Both leaves are traced arrays, so one compiled program serves any
    block-table/position contents (fixed engine shapes, variable routing).

    ``positions`` is the position of the FIRST token in the fed block: the
    decode tick feeds ``[b, 1]`` tokens (one per row at its own position);
    the chunked-prefill path feeds ``[1, chunk]`` tokens occupying positions
    ``positions[0] .. positions[0] + chunk - 1`` of one sequence.
    """

    block_tables: jax.Array  # [b, max_pages_per_seq] int32 page ids
    positions: jax.Array     # [b] int32 — position of tokens[:, 0] per row
    # RAGGED batch metadata (ISSUE 11).  None selects the legacy
    # decode/prefill dispatch; traced arrays route s == 1 batches to
    # paged_attention_ragged — the single-launch form a mixed
    # prefill+decode+verify tick runs on.  Data-carried, never static:
    # tick composition changes never recompile.
    #
    # ``horizons`` is each row's kv horizon in tokens, bucketed to
    # BUCKET(64)-token multiples (0 = dead padding row, touches no page).
    # When ``table_index`` is set, ``block_tables`` is COMPRESSED to the
    # tick's unique tables [T, max_pages] (one per decode slot + one per
    # packed prefilling request + the null table) and ``table_index``
    # maps each of the R rows to its table — rows of one span share one
    # table, so the fallback gathers each table's pages once instead of
    # once per row.
    horizons: Optional[jax.Array] = None     # [R] int32 or None
    table_index: Optional[jax.Array] = None  # [R] int32 into block_tables
    # which walks the Pallas kernel shares (:func:`plan_walks`), worked out
    # ONCE in front of a tick's layers: one a page class.  None: the kernel
    # reads it off its own call.  ``write_positions``: where a row's K/V
    # lands when ``positions`` is its MASK position (a block-causal model)
    walks: Optional[object] = None
    write_positions: Optional[jax.Array] = None


def paged_gather_kv(pool, block_tables: jax.Array, d: int, dtype=None,
                    layer=None, latent: bool = False):
    """Dense (keys, values), each ``[b, max_pages*page_size, nkv, d]``, of
    each row's pages: the logical view of the pool's row.

    The fallback's materialized gather — the tensor the Pallas kernel
    exists to avoid.  Quantized pools (ops/kv_quant.QuantPagedKV)
    dequantize at the gather, into ``dtype`` (the query/compute dtype);
    plain pools return the gathered values bitwise.  A latent pool's key
    row is its value too."""
    heads = kv_quant.dequant_gather(pool, block_tables, d, dtype, layer)
    return (heads, heads) if latent else kv_quant.split_kv(heads)


def plan_walks(pool, paged: PagedState, d: int, *,
               sliding_window: Optional[int] = None, latent: bool = False):
    """``ops/pallas/paged_attention.tile_shares`` of a ragged ``paged``
    state on ``pool`` (a page class's leaf, layered or not) under
    ``sliding_window``: what every layer's kernel call on that class would
    read off its own arguments, for ``paged.walks``.  None where the call
    does not take the kernel, or takes it a shard of the heads (a shard's
    row is not the pool's)."""
    from megatron_llm_tpu.core import parallel_state as ps

    if (paged.table_index is None or _kernel_refusal(pool, d, latent)
            or (ps.mesh_is_initialized()
                and ps.get_tensor_model_parallel_world_size() > 1)):
        return None
    values = kv_quant.values_of(pool)
    return pallas_paged.tile_shares(
        paged.block_tables, paged.table_index, paged.positions,
        paged.horizons, window=sliding_window, page=values.shape[-2],
        row_bytes=values.shape[-1] * values.dtype.itemsize)


def paged_attention_decode(
    q: jax.Array,             # [b, 1, n_heads, d] — queries at `positions`
    pool,                     # [num_pages, page_size, row] (kv_quant's row)
    block_tables: jax.Array,  # [b, max_pages_per_seq] int32 page ids
    positions: jax.Array,     # [b] int32 — q's position; attends to <= it
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    use_kernel: bool = True,
    layer=None,
    latent: bool = False,
) -> jax.Array:
    """One decode step of paged attention; returns [b, 1, n_heads, d].

    Row ``i`` attends to cache positions ``[max(0, pos-W+1), pos]`` of its
    own block table (the current token's K/V must already be written to its
    page — the engine writes-then-attends, matching the dense decode path
    in models/transformer.attention_sublayer).
    """
    assert q.ndim == 4 and q.shape[1] == 1, "decode expects [b, 1, n, d]"
    b, _, n, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    if _take_kernel("paged_decode", use_kernel, pool, d, latent):
        return _run_kernel(
            pallas_paged.paged_decode_kernel, q, pool, layer,
            (block_tables, positions),
            scale=scale, sliding_window=sliding_window, latent=latent,
        )

    k_all, v_all = paged_gather_kv(pool, block_tables, d, q.dtype, layer,
                                   latent)
    kv_len = k_all.shape[1]
    kv_pos = jnp.arange(kv_len)[None, :]
    allowed = kv_pos <= positions[:, None]
    if sliding_window is not None:
        allowed &= positions[:, None] - kv_pos < sliding_window
    bias = jnp.where(allowed, 0.0, attn_ops.NEG_INF).astype(jnp.float32)
    return attn_ops.xla_attention(
        q, k_all, v_all, bias=bias[:, None, None, :], scale=scale)


def paged_attention_ragged(
    q: jax.Array,             # [R, 1, n_heads, d] — one query row per entry
    pool,                     # [num_pages, page_size, row] (kv_quant's row)
    tables: jax.Array,        # [T, max_pages_per_seq] int32 — UNIQUE tables
    table_index: jax.Array,   # [R] int32 — each row's table
    positions: jax.Array,     # [R] int32 — each row's own position
    horizons: jax.Array,      # [R] int32 — bucketed kv horizon (0 = dead row)
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    use_kernel: bool = True,
    layer=None,
    latent: bool = False,
    walks=None,
) -> jax.Array:
    """One RAGGED batch of paged attention; returns [R, 1, n_heads, d].

    The ragged decomposition (PAPERS.md "Ragged Paged Attention"): a tick's
    heterogeneous work — decode slots (query span 1), speculative-verify
    blocks (span k+1) and prefill chunks (span = chunk rows) — is flattened
    into R single-token rows, each carrying its own (position, kv horizon,
    block table).  Block tables arrive COMPRESSED: rows of one span share
    one entry of ``tables`` and ``table_index`` names it.  The fallback
    gathers each table's pages once, so a 64-row chunk reads its pages
    once, not 64 times; the kernel reads the same fact off the rows
    (ops/pallas/paged_attention.tile_shares): the rows of a tile of 8 that
    name ONE table share ONE page walk and one matmul a kv head through
    the last block any of them sees, whatever their positions, so a 64-row
    chunk is walked 8 times and a diffusion block's denoise and commit
    rows once.  Rows of DIFFERENT sequences in one tile whose tables
    name the same pages over whole compute blocks — decode rows on one
    cached prefix, which the tick lays side by side
    (generation/ragged.decode_order) — share one walk of those blocks, and
    each walks what is its own (the blocks before the last window start
    among them, its own pages behind the prefix) for itself; a row with no
    such neighbours walks once for itself.  One launch serves any mix; the
    composition lives entirely in the data-carried metadata, so changing
    it never recompiles.

    Numerics contract (tests/test_ragged_tick.py): row ``i`` computes the
    s=1 decode attention at ``positions[i]`` over its own table — bitwise
    what :func:`paged_attention_decode` produces for that row (per-row
    bits are batch-size invariant, and batching scores over the unique
    tables then selecting a row's table is bitwise the per-row gather),
    which is also bitwise what a chunked prefill produces for the same
    (tokens, positions) because masked attention is invariant to
    query-row partitioning when kv horizons stay on the BUCKET(64) grid.
    ``horizons`` bounds the page walk in the Pallas kernel (a dead row —
    horizon 0 — skips every page); the fallback's mask ``kv_pos <=
    positions`` subsumes them.

    ``latent`` is the latent pool of MLA (models/transformer.py
    ``_mla_paged``): one shared key row a token whose own values are the
    value, so the output has the key's width and the caller keeps the
    leading ``kv_lora_rank`` values.

    ``walks`` is :func:`plan_walks` of these very arguments, where a caller
    worked it out once for a tick's layers (``PagedState.walks``).
    """
    assert q.ndim == 4 and q.shape[1] == 1, "ragged rows are [R, 1, n, d]"
    b, _, n, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    if _take_kernel("paged_ragged", use_kernel, pool, d, latent):
        return _run_kernel(
            pallas_paged.paged_ragged_kernel, q, pool, layer,
            (tables, table_index, positions, horizons),
            scale=scale, sliding_window=sliding_window, latent=latent,
            shares=walks,
        )

    # fallback: gather each UNIQUE table's pages once, batch the score
    # matmul over all T tables, select each row's table, softmax only the
    # selected scores, then scatter the probs back through a one-hot so
    # the context matmul keeps the shared [T, kv] v layout (no per-row
    # gather ever materializes) — bitwise the per-row-gathered
    # xla_attention decode fallback (same contractions, same per-(row,
    # table) reduction order; only the batching layout moves)
    T = tables.shape[0]
    # [T, kv, nkv, d]
    k_all, v_all = paged_gather_kv(pool, tables, d, q.dtype, layer, latent)
    kv_len, nkv = k_all.shape[1:3]
    g = n // nkv
    qg = q.reshape(b, 1, nkv, g, d)
    # [R, T, nkv, g, 1, kv] — the decode fallback's "bqhgd,bkhd->bhgqk"
    # with the table dim batched
    scores = jnp.einsum("bqhgd,tkhd->bthgqk", qg * scale, k_all)
    scores = scores.astype(jnp.float32)
    idx6 = table_index[:, None, None, None, None, None]
    s_sel = jnp.take_along_axis(scores, idx6, axis=1)[:, 0]
    kv_pos = jnp.arange(kv_len)[None, :]
    allowed = kv_pos <= positions[:, None]
    if sliding_window is not None:
        allowed &= positions[:, None] - kv_pos < sliding_window
    bias = jnp.where(allowed, 0.0, attn_ops.NEG_INF).astype(jnp.float32)
    s_sel = s_sel + bias[:, None, None, None, :]
    p_sel = jax.nn.softmax(s_sel, axis=-1).astype(v_all.dtype)
    onehot = (jnp.arange(T)[None, :]
              == table_index[:, None]).astype(v_all.dtype)      # [R, T]
    p_full = p_sel[:, None] * onehot[:, :, None, None, None, None]
    out = jnp.einsum("bthgqk,tkhd->bthgqd", p_full, v_all)
    sel = jnp.take_along_axis(out, idx6, axis=1)[:, 0]
    # [R, nkv, g, 1, d] -> [R, 1, n, d]
    return sel.transpose(0, 3, 1, 2, 4).reshape(b, 1, n, d)


def paged_attention_prefill(
    q: jax.Array,             # [b, s, n_heads, d] — chunk queries
    pool,                     # [num_pages, page_size, row] (kv_quant's row)
    block_tables: jax.Array,  # [b, kv_pages] int32 — pages covering the chunk
    start: jax.Array,         # [b] int32 — position of q[:, 0]
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    use_kernel: bool = True,
    layer=None,
    latent: bool = False,
) -> jax.Array:
    """One prefill CHUNK of paged attention; returns [b, s, n_heads, d].

    Query row ``j`` of sequence ``i`` sits at position ``start[i] + j`` and
    attends to cache positions ``<= start[i] + j`` of ``i``'s block table —
    the prefix-length-aware prefill-against-block-table mode: earlier pages
    may have been written by a previous chunk, by a different request's
    prefill (shared prefix-cache pages), or by this very call (the engine
    writes the chunk's own K/V through the block table before attending,
    matching the decode tick's write-then-attend order).

    ``block_tables`` is normally SLICED to the chunk's page horizon
    (``ceil((start + s) / page_size)`` pages, possibly bucket-padded with
    null pages) so the gather/grid cost scales with the attended context,
    not the sequence budget.  Padding pages past a row's context are fully
    masked — exact zeros after softmax, identical numerics either way.
    """
    assert q.ndim == 4, "prefill expects [b, s, n, d]"
    b, s, n, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    if _take_kernel("paged_prefill", use_kernel, pool, d, latent):
        return _run_kernel(
            pallas_paged.paged_prefill_kernel, q, pool, layer,
            (block_tables, start),
            scale=scale, sliding_window=sliding_window, latent=latent,
        )

    k_all, v_all = paged_gather_kv(pool, block_tables, d, q.dtype, layer,
                                   latent)
    kv_len = k_all.shape[1]
    q_pos = start[:, None, None] + jnp.arange(s)[None, :, None]  # [b, s, 1]
    kv_pos = jnp.arange(kv_len)[None, None, :]
    allowed = kv_pos <= q_pos
    if sliding_window is not None:
        allowed &= q_pos - kv_pos < sliding_window
    bias = jnp.where(allowed, 0.0, attn_ops.NEG_INF).astype(jnp.float32)
    return attn_ops.xla_attention(
        q, k_all, v_all, bias=bias[:, None, :, :], scale=scale)


def _kernel_refusal(pool, d: int, latent: bool = False) -> Optional[str]:
    """Why the Pallas kernel cannot serve this call (None: it can).

    The kernel copies whole pages of the pool's stored row (ops/kv_quant.py:
    a head's key and value side by side) and slices a head's lanes out of
    the copy in whole 128-lane groups, so Mosaic needs a head's PAIR, ``2 *
    d`` lanes, to be a multiple of 128: Mistral/Mixtral/Llama (d=128) and
    d=256 read key and value as two aligned slices, Falcon-7B (one kv head
    of 64) and Falcon-40B (8 of 64) read the 128-lane pair as one operand
    (ops/pallas/paged_attention._head_lanes); a head of 32 or 96 does not
    take the kernel.  A latent row is one head and has to be whole lanes
    itself.  Pages need 8 sublane rows; bf16, int8 and fp8 pools all lower
    from 8 rows up (packed dtypes are unpacked after the copy).
    """
    from megatron_llm_tpu.core.parallel_state import target_platform

    page_size, row = kv_quant.values_of(pool).shape[-2:]
    target = target_platform()
    if target != "tpu":
        return f"target platform is {target}"
    if row % 128 if latent else (2 * d) % 128:
        return (f"latent row of {row} values" if latent else
                f"head_dim {d}: a head's key|value pair of {2 * d} values"
                ) + " is not a multiple of 128 lanes"
    if page_size % 8:
        return f"page_size {page_size} is not a multiple of 8 sublanes"
    return None


def _take_kernel(op: str, use_kernel: bool, pool, d: int,
                 latent: bool) -> bool:
    refusal = ("use_flash_attn is off" if not use_kernel
               else _kernel_refusal(pool, d, latent))
    attn_ops.announce_path(op, "jnp" if refusal else "pallas", refusal or "")
    return refusal is None


def _run_kernel(kernel, q, pool, layer, tables, **kw):
    """Call a Pallas paged kernel on the flat view of the (layered) pool;
    under a tp > 1 mesh, shard_map it over the heads (q heads and the
    pool row's kv heads split the same way the qkv column-parallel rule
    splits them — a head's key|value pair stays on one shard; block tables
    and positions are replicated) — pallas_call is opaque to the GSPMD
    partitioner."""
    from megatron_llm_tpu.core import parallel_state as ps

    flat, base = kv_quant.layer_view(pool, layer)
    if (not ps.mesh_is_initialized()
            or ps.get_tensor_model_parallel_world_size() == 1):
        return kernel(q, flat, *tables, page_base=base, **kw)

    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu.parallel.compat import shard_map

    mesh, names = attn_ops.kernel_region(ps.get_global_mesh())
    heads = P(None, None, ps.TP_AXIS, None)
    rows = P(None, None, ps.TP_AXIS)
    spec = (kv_quant.QuantPagedKV(q=rows, scale=P(None, ps.TP_AXIS))
            if kv_quant.is_quantized(flat) else rows)
    return shard_map(
        lambda q_, pool_, base_, *t: kernel(
            q_, pool_, *t, page_base=base_, **kw),
        mesh=mesh, in_specs=(heads, spec, P()) + (P(),) * len(tables),
        out_specs=heads, axis_names=names, check_vma=False,
    )(q, flat, jnp.asarray(base, jnp.int32), *tables)
