"""Latent attention under a learned indexer (DeepSeek-V3.2's sparse
attention; A.X-K2): what :func:`models.transformer.mla_sublayer` adds where
``index_topk`` is set (``_mla_rows``, ``_mla_mask`` at that file's end).

Per token ``t`` of a layer, from the layer's normed input ``u`` and the
query latent ``c_q`` the attention already made:

* index queries ``q^I_{t,j} = (c_q W^I_q)_j``, ``index_n_heads`` of
  ``index_head_dim``; ONE index key ``k^I_t = LayerNorm(u W^I_k)``; RoPE on
  the first ``qk_rope_head_dim`` values of each, HALVES paired (the
  released V3.2 inference code's form, not the interleaved pairs of the
  attention's own rope dims); head weights ``w_t = u W^I_w / sqrt(heads x
  dim)``;
* ``I[t, s] = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)`` for ``s <= t``;
* the token attends the ``index_topk`` positions of largest ``I[t, .]``
  (ties to the earlier), all of them up to that context, and no other.

The cache holds ``k^I`` beside the latent row, a leaf of its own under
the same page ids (``ops/kv_quant.IndexedLatent``).  The engine's tick
(:func:`paged`) writes both, then sweeps, selects and attends a tile of rows
at a time (``ops/sparse_attention.py``: a prompt chunk's rows share their
table's blocks, a decode row gathers its picked rows); the dense forward (:func:`dense_bias`) is
the same selection as a mask over full attention.  The indexer is not
trained here: its scores enter no loss and the selection passes no
gradient (the V3.2 report trains it on a loss of its own).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.ops import attention as attn_ops
from megatron_llm_tpu.ops import kv_quant
from megatron_llm_tpu.ops import sparse_attention as sparse_ops
from megatron_llm_tpu.ops.norms import init_norm_params, layer_norm
from megatron_llm_tpu.ops.rope import apply_rotary_emb_half


def init_index_params(cfg, key: jax.Array) -> dict:
    """The indexer's leaves of a layer's ``attention`` subtree."""
    m = cfg.model
    std = m.init_method_std
    hi, di = m.index_n_heads, m.index_head_dim
    kq, kk, kw = jax.random.split(key, 3)
    normal = lambda k_, shape: std * jax.random.normal(      # noqa: E731
        k_, shape, jnp.float32)
    return {
        "index_q": {"kernel": normal(kq, (m.q_lora_rank, hi * di))},
        "index_k": {"kernel": normal(kk, (m.hidden_size, di))},
        "index_k_norm": init_norm_params(di, False),
        "index_w": {"kernel": normal(kw, (m.hidden_size, hi))},
    }


def index_inputs(cfg, p: dict, x: jax.Array, c_q: jax.Array, rope,
                 position_ids, linear):
    """``(q^I [b, s, heads, dim], k^I [b, s, dim], w [b, s, heads]
    float32)`` of the tokens ``x`` [b, s, h] (the layer's normed input)
    with query latents ``c_q``."""
    m = cfg.model
    b, s, _ = x.shape
    hi, di, rd = m.index_n_heads, m.index_head_dim, m.qk_rope_head_dim
    cos, sin = rope
    q = linear(p["index_q"], c_q).reshape(b, s, hi, di)
    k = linear(p["index_k"], x)
    k = layer_norm(k, p["index_k_norm"]["scale"], p["index_k_norm"]["bias"],
                   m.layernorm_epsilon)[:, :, None]              # [b, s, 1, di]
    rot = lambda t: jnp.concatenate(                             # noqa: E731
        [apply_rotary_emb_half(t[..., :rd], cos, sin, position_ids),
         t[..., rd:]], axis=-1)
    w = linear(p["index_w"], x).astype(jnp.float32) * (hi * di) ** -0.5
    return rot(q), rot(k)[:, :, 0], w


def selected(scores: jax.Array, allowed: jax.Array, k: int) -> jax.Array:
    """The mask of the ``k`` largest ``scores`` [..., s] among ``allowed``
    a row, ties to the earlier position; all of ``allowed`` where a row
    has at most ``k``."""
    scores = jnp.where(allowed, scores, -jnp.inf)
    if scores.shape[-1] <= k:
        return allowed
    kth = jax.lax.top_k(scores, k)[0][..., -1:]
    above, tie = scores > kth, scores == kth
    need = k - above.sum(-1, keepdims=True)
    return (above | (tie & (jnp.cumsum(tie, axis=-1) <= need))) & allowed


def dense_bias(cfg, q: jax.Array, k: jax.Array, w: jax.Array,
               segment_ids=None) -> jax.Array:
    """The selection as an attention bias ``[b, 1, s, s]`` (0 where query
    ``t`` attends key ``s``, ``NEG_INF`` elsewhere) for the no-cache
    forward: causal, inside a packed segment, the ``index_topk`` best."""
    s = q.shape[1]
    scores = jnp.einsum("bthd,bsd->bths", q, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.einsum("bth,bths->bts", w, jnp.maximum(scores, 0.0))
    allowed = jnp.tril(jnp.ones((s, s), bool))[None]
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None])
    keep = selected(scores + 0.0, allowed, cfg.model.index_topk)
    return jnp.where(keep, 0.0, attn_ops.NEG_INF).astype(jnp.float32)[:, None]


def paged(cfg, q_nope, q_rope, c_kv, k_rope, w_ukv, cache, state, scale, *,
          p: dict, x, c_q, rope, position_ids, linear):
    """The absorbed form against the paged pool, every fed token a row at
    its own position (a tick's ``[R, 1]`` rows, a scoring chunk's ``[b,
    s]``): write the rows' latent AND index key through the block table,
    score every row against its sequence's index keys, pick, and attend
    the picked latent rows alone.  Returns (context [b, s, n, v], pool)."""
    m = cfg.model
    b, s, n, nope = q_nope.shape
    r = m.kv_lora_rank
    pool, layer = cache
    assert isinstance(pool, kv_quant.IndexedLatent), (
        "learned sparse attention keeps index keys beside the latent rows")
    page_size, width = pool.rows.shape[2:]
    rows = b * s
    pos = (state.positions[:, None] + jnp.arange(s)[None, :]).reshape(rows)
    if state.table_index is not None:
        tables, index = state.block_tables, state.table_index
        assert s == 1
    else:
        tables = state.block_tables
        index = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
    # a dead padding row (horizon 0) scores and attends nothing
    ctx = pos + 1 if state.horizons is None else jnp.where(
        state.horizons > 0, pos + 1, 0)
    # clip: as the K/V pair's write (attention_sublayer), stray rows land
    # in pages that are never attended
    page_ids = tables[index, jnp.clip(pos // page_size, 0,
                                      tables.shape[1] - 1)][:, None]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1).reshape(rows, 1, 1, -1)
    pad = width - latent.shape[-1]
    if pad:
        latent = jnp.pad(latent, ((0, 0),) * 3 + ((0, pad),))
    q_i, k_i, w_i = index_inputs(cfg, p, x, c_q, rope, position_ids, linear)
    pool = kv_quant.IndexedLatent(
        rows=kv_quant.paged_write(
            pool.rows, page_ids, (pos % page_size)[:, None], latent, layer),
        index=sparse_ops.write_rows(
            pool.index, layer, page_ids[:, 0], pos % page_size,
            k_i.reshape(rows, -1)))
    q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_ukv[..., :nope])
    q_abs = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(rows, n, -1)
    if pad:
        q_abs = jnp.pad(q_abs, ((0, 0),) * 2 + ((0, pad),))
    u = sparse_ops.sparse_attention(
        q_i.reshape(rows, *q_i.shape[2:]), w_i.reshape(rows, -1), q_abs,
        pool.index, pool.rows, layer, tables, index, ctx, m.index_topk,
        scale, r)
    out = jnp.einsum("bsnr,rnd->bsnd", u.reshape(b, s, n, r),
                     w_ukv[..., nope:])
    return out, pool
