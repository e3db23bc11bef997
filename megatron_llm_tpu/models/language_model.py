"""Full language model: embedding + transformer + output head + loss.

Replaces megatron/model/language_model.py (Embedding:133,
TransformerLanguageModel:329, parallel_lm_logits:24) and
megatron/model/gpt_model.py (post_language_model_processing:18).

Under pjit the vocab dimension of the embedding table / LM head carries a
``tp`` sharding (vocab-parallel, VocabParallelEmbedding semantics) and XLA
inserts the all-reduces the reference issues by hand (layers.py:187-210).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.core import rng as rng_mod
from megatron_llm_tpu.models.moe import zero_aux
from megatron_llm_tpu.models.transformer import (
    init_stacked_layers,
    layer_stacks,
    transformer_forward,
)
from megatron_llm_tpu.ops.cross_entropy import (
    chunked_softmax_cross_entropy_from_hidden,
    softmax_cross_entropy,
)
from megatron_llm_tpu.ops.norms import init_norm_params, norm
from megatron_llm_tpu.ops.rope import precompute_freqs
from megatron_llm_tpu.parallel import overlap as tp_overlap_mod
from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

Params = Dict[str, Any]


def pad_vocab(vocab_size: int, divisible_by: int, tp: int) -> int:
    """Pad vocab to a multiple of ``divisible_by * tp``
    (reference tokenizer.py:_vocab_size_with_padding:49-62)."""
    multiple = divisible_by * tp
    return multiple * ((vocab_size + multiple - 1) // multiple)


def padded_vocab_size(vocab_size: int, cfg) -> int:
    return pad_vocab(
        vocab_size,
        cfg.model.make_vocab_size_divisible_by,
        cfg.parallel.tensor_model_parallel_size,
    )


def init_model_params(cfg, key: jax.Array) -> Params:
    m = cfg.model
    assert m.vocab_size is not None, "cfg.model.vocab_size must be set"
    v = padded_vocab_size(m.vocab_size, cfg)
    h = m.hidden_size
    k_emb, k_layers, k_head, k_pos = jax.random.split(key, 4)
    params: Params = {
        "embedding": {
            "word_embeddings": m.init_method_std
            * jax.random.normal(k_emb, (v, h), jnp.float32)
        },
        "layers": init_stacked_layers(cfg, k_layers),
        "final_norm": init_norm_params(h, m.use_rms_norm, bias=m.norm_bias,
                                       gain=m.norm_gain, **_gated(m, key)),
    }
    if m.linear_layout is not None or m.sublayer_pattern:
        # a hybrid's mixers, a stack a kind beside the uniform stack
        from megatron_llm_tpu.models.sublayers import init_mixers

        params["mixers"] = init_mixers(cfg, jax.random.fold_in(k_layers, 2))
    if m.loop_steps > 1:   # a looped stack's exit gate (the file's end)
        params["exit_gate"] = init_exit_gate(cfg, jax.random.fold_in(key, 3))
    if m.dense_prefix_layers:   # a stack of their own: ONE scanned shape
        params["dense_layers"] = init_stacked_layers(
            cfg, jax.random.fold_in(k_layers, 1), m.dense_prefix_layers,
            dense_ffn=True)
    if m.position_embedding_type == "absolute":
        params["embedding"]["position_embeddings"] = m.init_method_std * (
            jax.random.normal(k_pos, (m.max_position_embeddings, h), jnp.float32)
        )
    if m.num_tokentypes > 0:
        # BERT segment embeddings (reference Embedding tokentype path,
        # language_model.py:173-183)
        k_tt = jax.random.fold_in(k_pos, 1)
        params["embedding"]["tokentype_embeddings"] = m.init_method_std * (
            jax.random.normal(k_tt, (m.num_tokentypes, h), jnp.float32)
        )
    if not m.tie_embed_logits:
        # untied lm_head (language_model.py:436-457)
        params["lm_head"] = {
            "kernel": m.init_method_std
            * jax.random.normal(k_head, (h, v), jnp.float32)
        }
    return params


def make_rope_cache(cfg) -> Optional[Tuple[jax.Array, jax.Array]]:
    m = cfg.model
    if m.position_embedding_type != "rotary":
        return None
    return precompute_freqs(
        m.kv_channels,
        m.max_position_embeddings,
        theta=m.rope_theta,
        scaling_factor=m.rope_scaling_factor,
        scaling_type=m.rope_scaling_type,
        llama3_params=dict(
            low_freq_factor=m.rope_llama3_low_freq_factor,
            high_freq_factor=m.rope_llama3_high_freq_factor,
            original_max_position=m.rope_llama3_original_max_position,
        ),
        yarn_params=dict(
            beta_fast=m.rope_yarn_beta_fast,
            beta_slow=m.rope_yarn_beta_slow,
            original_max_position=m.rope_yarn_original_max_position,
        ),
    )


@functools.lru_cache(maxsize=None)
def _take_rows_matmul_bwd(rows: int, chunk: int, table_dtype: str):
    """``take(table, ids, axis=0)`` whose BACKWARD is a one-hot matmul
    (``dtable = one_hot(ids).T @ g``, token-chunked) instead of the take
    transpose's scatter-add.

    Two TPU reasons: (1) scatter is the one op class the MXU cannot touch;
    (2) XLA's scatter *partitioner* CHECK-crashes
    (spmd_partitioner_util.cc:506, ExpandDeviceGroupsWithIota) when this
    scatter-add sits inside the 1F1B tick loop under the pipeline's
    partial-manual shard_map with a nested-manual flash region and
    dp-sharded ZeRO-1 state — the round-4 "pp x dp>1 x tp>1 flash
    fallback" root cause (tools/flash_nested_repro.py). The forward is the
    unchanged gather; only the vjp differs (same additive semantics,
    accumulated in the cotangent dtype like the scatter it replaces).
    """
    import numpy as np

    @jax.custom_vjp
    def take(table, ids):
        return jnp.take(table, ids, axis=0)

    def fwd(table, ids):
        return jnp.take(table, ids, axis=0), ids

    def bwd(res, g):
        ids, tdt = res, jnp.dtype(table_dtype)
        h = g.shape[-1]
        n = int(np.prod(ids.shape))
        gf = g.reshape(n, h)
        idf = ids.reshape(n)
        # largest divisor of n that fits the chunk budget — requiring exact
        # divisibility by 4096 would silently fall back to one unbounded
        # [n, rows] one-hot for e.g. n=6144 (the transient this bounds)
        c = next((d for d in range(min(chunk, n), 0, -1) if n % d == 0), n)
        if c < n:
            # bound the [n, rows] one-hot transient (1 GiB at n=4096,
            # vocab 128k, bf16) by accumulating over token chunks
            def body(acc, xs):
                i_c, g_c = xs
                oh = jax.nn.one_hot(i_c, rows, dtype=g_c.dtype)
                return acc + jnp.matmul(
                    oh.T, g_c, preferred_element_type=acc.dtype), None

            acc0 = jnp.zeros((rows, h), g.dtype)
            dtable, _ = jax.lax.scan(
                body, acc0,
                (idf.reshape(n // c, c), gf.reshape(n // c, c, h)))
        else:
            oh = jax.nn.one_hot(idf, rows, dtype=gf.dtype)
            dtable = jnp.matmul(oh.T, gf, preferred_element_type=gf.dtype)
        return dtable.astype(tdt), np.zeros(ids.shape, jax.dtypes.float0)

    take.defvjp(fwd, bwd)
    return take


# Bound on the [chunk, rows] one-hot transient in the matmul backward of
# the 1F1B embedding path (sized for the fp32 worst case regardless of
# table dtype — the transient is built in the COTANGENT dtype, which a
# generic caller may keep wider than the table): chunk 512 at vocab 32k,
# 128 at 128k. 64 MiB keeps the per-tick bwd transient small next to the
# full-logits footprint the pipelined CE is certified against
# (tests/test_pipeline.py::test_gpipe_ce_memory_bounded) while still
# giving the MXU large tiles.
_EMBED_BWD_ONE_HOT_CAP_BYTES = 64 * 2 ** 20


def _embed_take(cfg, table: jax.Array, ids: jax.Array) -> jax.Array:
    """Embedding-table row lookup.

    Under the 1F1B schedules the gradient is the matmul form
    (:func:`_take_rows_matmul_bwd`): their per-tick vjp puts the take
    transpose's scatter-add inside the pp shard_map's tick loop, where
    XLA's scatter partitioner CHECK-crashes (the round-4 pp x dp>1 x tp>1
    blocker). GPipe keeps the plain take/scatter — its whole-batch
    embedding sits outside the tick loop, partitions fine (verified by
    the round-5 bisection), and the scatter is cheaper in memory than
    even a chunked one-hot."""
    if (cfg.parallel.pipeline_model_parallel_size > 1
            and cfg.parallel.pipeline_schedule != "gpipe"):
        rows = table.shape[0]
        c = max(128, _EMBED_BWD_ONE_HOT_CAP_BYTES // (rows * 4))
        c = 1 << (int(c).bit_length() - 1)  # power of two: stable divisors
        return _take_rows_matmul_bwd(rows, c, str(table.dtype))(table, ids)
    return jnp.take(table, ids, axis=0)


def embed_tokens(
    cfg, params: Params, tokens: jax.Array,
    position_ids: Optional[jax.Array] = None,
    tokentype_ids: Optional[jax.Array] = None,
) -> jax.Array:
    emb = params["embedding"]["word_embeddings"]
    hidden = _embed_take(cfg, emb, tokens)
    if cfg.model.position_embedding_type == "absolute":
        pos = position_ids if position_ids is not None else jnp.arange(tokens.shape[1])[None]
        hidden = hidden + _embed_take(
            cfg, params["embedding"]["position_embeddings"], pos)
    if tokentype_ids is not None:
        hidden = hidden + _embed_take(
            cfg, params["embedding"]["tokentype_embeddings"], tokentype_ids
        )
    return hidden.astype(_compute_dtype(cfg))


def head_weight(cfg, params: Params) -> jax.Array:
    """The LM-head kernel [h, v]: the transposed tied embedding table or the
    untied lm_head (language_model.py:24-53 tie handling) — single source of
    truth for every head consumer (compute_logits, chunked CE, pipeline)."""
    if cfg.model.tie_embed_logits:
        return params["embedding"]["word_embeddings"].T
    return params["lm_head"]["kernel"]


def compute_logits(cfg, params: Params, hidden: jax.Array) -> jax.Array:
    """parallel_lm_logits analog (language_model.py:24-53): tied or untied head.

    With ``--vocab_ring`` active (parallel/overlap.py:vocab_parallel) the
    head GEMM + logits all-gather run as an all-gather matmul ring;
    inactive/ineligible calls take the plain fallback byte for byte."""
    return tp_overlap_mod.vocab_parallel(
        cfg, head_weight(cfg, params), hidden,
        lambda w, x: x @ w.astype(x.dtype))


def _compute_dtype(cfg):
    return {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "float16": jnp.float16,
    }[cfg.training.params_dtype]


def model_forward(
    cfg,
    params: Params,
    tokens: jax.Array,  # [b, s] int32
    *,
    position_ids: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    token_idx: Optional[jax.Array] = None,
    labels: Optional[jax.Array] = None,
    loss_mask: Optional[jax.Array] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    rope_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    kv_caches=None,
    cache_index=None,
    paged=None,
    sp_constraint=None,
    logits_postprocess=True,
    return_aux=False,
):
    """GPTModel.forward analog (gpt_model.py:45-124).  ``paged``
    (ops/paged_attention.PagedState): ``kv_caches`` is the paged pool, one
    leaf [L, num_pages, page_size, row] (ops/kv_quant.py owns the row) that
    every layer updates in place, instead of a dense cache, and every batch
    row decodes one token at its own ``paged.positions`` entry (the serving
    engine's fused tick, generation/engine.py).  With ``labels``: per-token
    fp32 loss [b, s] (the masked mean is the caller's job, as the reference
    loss_func splits it); without: logits.  Returns (output, new_kv_caches),
    with ``return_aux`` also moe_aux[AUX_LEN] (MoE losses, models/moe.py)."""
    hidden = embed_tokens(cfg, params, tokens, position_ids)
    if dropout_key is not None and not deterministic:
        k_embed, dropout_key = jax.random.split(dropout_key)
        hidden = rng_mod.dropout(k_embed, cfg.model.hidden_dropout, hidden)
    if sp_constraint is not None:
        hidden = sp_constraint(hidden)

    if rope_cache is None:
        rope_cache = make_rope_cache(cfg)
    if cfg.model.loop_steps > 1:    # a looped stack's passes: the file's end
        assert deterministic and token_idx is None and sp_constraint is None
        return looped_forward(cfg, params, hidden, rope_cache, position_ids,
                              segment_ids, labels, kv_caches, cache_index,
                              paged, logits_postprocess, return_aux)
    ppc = pp_serve_mod.current()
    if ppc is not None and paged is not None and kv_caches is not None:
        # Pipeline-parallel serving tick (parallel/pp_serve.py, ISSUE 20):
        # the layer stack runs as pp stages over microbatched rows, each
        # stage reading/writing only its own layers' slice of the paged
        # pool.  MoE aux is not plumbed (deterministic inference).
        hidden, new_caches = pp_serve_mod.pipelined_transformer(
            cfg, ppc, params["layers"], hidden,
            rope=rope_cache, position_ids=position_ids,
            kv_caches=kv_caches, paged=paged,
        )
        moe_aux = zero_aux()
    else:
        new_caches, moe_aux = kv_caches, None
        for stack, first_layer in layer_stacks(cfg, params):
            # a paged pool is one leaf over all layers, handed from stack
            # to stack; a dense cache is a stack's own
            assert first_layer == 0 or kv_caches is None or paged is not None
            hidden, new_caches, aux = transformer_forward(
                cfg, stack, hidden,
                rope=rope_cache, position_ids=position_ids,
                segment_ids=segment_ids, token_idx=token_idx,
                dropout_key=dropout_key, deterministic=deterministic,
                kv_caches=new_caches, cache_index=cache_index, paged=paged,
                sp_constraint=sp_constraint, layer_offset=first_layer,
            )
            moe_aux = aux if moe_aux is None else moe_aux + aux

    hidden = norm(hidden, params["final_norm"], cfg.model.layernorm_epsilon,
                  cfg.model.use_rms_norm)

    def ret(out):
        return (out, new_caches, moe_aux) if return_aux else (out, new_caches)

    if not logits_postprocess:
        return ret(hidden)

    # one name for what follows the final norm, in the trainer's step and
    # the engine's tick alike: a device trace prices the head (+ loss)
    with jax.named_scope("lm_head_loss"):
        if labels is not None and cfg.model.ce_vocab_chunks:
            # head matmul fused into a vocab-chunked CE: the [b, s, vocab]
            # fp32 logits are never materialized (large-vocab memory lever)
            loss = chunked_softmax_cross_entropy_from_hidden(
                hidden, head_weight(cfg, params).astype(hidden.dtype),
                labels, cfg.model.ce_vocab_chunks,
            )
            return ret(loss)

        logits = compute_logits(cfg, params, hidden)
        if labels is None:
            return ret(logits)

        loss = softmax_cross_entropy(logits, labels)  # fp32 per-token
        return ret(loss)


def loss_from_batch(cfg, params, batch: Dict[str, jax.Array], *,
                    dropout_key=None, deterministic=True, rope_cache=None,
                    sp_constraint=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Standard LM loss over a batch dict with keys
    tokens/labels/loss_mask[/position_ids/segment_ids].

    Mirrors the reference loss_func (finetune.py:139-190): masked mean of the
    per-token CE. MoE models add the weighted router losses (models/moe.py)
    to the trained total while still reporting "lm loss" as the bare CE.
    """
    moe = cfg.model.num_experts is not None
    out = model_forward(
        cfg, params, batch["tokens"],
        position_ids=batch.get("position_ids"),
        segment_ids=batch.get("segment_ids"),
        token_idx=batch.get("token_idx"),
        labels=batch["labels"],
        dropout_key=dropout_key,
        deterministic=deterministic,
        rope_cache=rope_cache,
        sp_constraint=sp_constraint,
        return_aux=moe,
    )
    per_token = out[0]
    mask = batch["loss_mask"].astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_token * mask).sum() / denom
    metrics = {"lm loss": loss}
    if moe:
        from megatron_llm_tpu.models.moe import aux_loss_coeffs

        balance, z = out[2][0], out[2][1]
        c_bal, c_z = aux_loss_coeffs(cfg)
        total = loss + c_bal * balance + c_z * z
        metrics["moe aux loss"] = balance
        # what the routers did, summed over the expert layers: assignments
        # made, those whose expert is held here and ran, and held ones that
        # found the row buffer full (models/moe.py; the trainer's
        # `train-moe` span carries them)
        metrics["moe assignments"] = out[2][2]
        metrics["moe held"] = out[2][4]
        metrics["moe dropped"] = out[2][5]
        if c_z:
            metrics["router z loss"] = z
        return total, metrics
    return loss, metrics


# ---- a looped stack (``loop_steps`` > 1) ------------------------------------
#
# Appended here: the lines above keep their numbers, which the serving
# tick's kernels carry in their payloads (tools/tick_digest.py).


# what a looped stack refuses, a sentence each, raised where the request
# enters: a checkpoint's config (weights_conversion/hf_to_native.py), the
# trainer's start-up (training_step.py)
EXIT_BELOW_ONE = (
    "early_exit_threshold {threshold} (below 1): reading an earlier pass's "
    "logits saves no compute without a K/V policy for the passes a token "
    "skipped (later tokens' queries of those passes read its keys there), "
    "which the config does not give; a looped stack reads the last pass's "
    "logits, the published threshold of 1.0")
LOOP_NOT_TRAINED = (
    "a looped stack (loop_steps {loops}) is served and not trained: the "
    "published objective weighs the passes' losses by the exit distribution "
    "with an entropy term whose coefficient is no key of the config")


def init_exit_gate(cfg, key: jax.Array) -> Params:
    """The exit gate of a looped stack: a hidden -> 1 linear with a bias,
    read after every pass's final norm."""
    m = cfg.model
    return {"kernel": m.init_method_std * jax.random.normal(
                key, (m.hidden_size, 1), jnp.float32),
            "bias": jnp.zeros((1,), jnp.float32)}


def exit_pdf(gates: jax.Array) -> jax.Array:
    """The exit distribution over the passes from the gates' logits
    ``[T, ...]``: with lambda_t = sigmoid(g_t), p_t = lambda_t prod_{j<t}
    (1 - lambda_j) for t < T and p_T = prod_{j<T} (1 - lambda_j), float32;
    it sums to one, and the last gate is read by nothing."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)      # prod_{j<=t}, t < T
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before, stay[-1:]])


def looped_forward(cfg, params: Params, hidden, rope_cache, position_ids,
                   segment_ids, labels, kv_caches, cache_index, paged,
                   logits_postprocess, return_aux):
    """:func:`model_forward` from the embedding on for a looped stack: the
    stack ``loop_steps`` times over the SAME parameters, the final norm
    after every pass (it feeds the next), the exit gate after every norm.

    The passes are ONE traced body, a scan over t around the layer scan.
    Pass t's layer l keeps its keys on slot ``t * depth + l`` of the cache
    (t from 0): the paged pool rides both scans' carries and is written in
    place, ``pool_first_layer`` the traced ``-t * depth``; the dense
    incremental cache's ``[T * L, ...]`` pair is scanned a pass.  The
    logits are the LAST pass's, which is where the exit distribution's
    cumulative mass reaches the published threshold of 1
    (:data:`EXIT_BELOW_ONE` says why no lower one is taken).  With
    ``return_aux`` the third output is a PAIR: the routers' aux vector
    summed over the passes, and the exit distribution ``[b, s, T]``
    (:func:`exit_pdf`)."""
    m = cfg.model
    T, L = m.loop_steps, m.depth
    assert pp_serve_mod.current() is None, "a looped stack has no stages"
    (stack, first_layer), = layer_stacks(cfg, params)
    in_pool = paged is not None and kv_caches is not None
    per_pass = None if in_pool else jax.tree.map(
        lambda a: a.reshape(T, L, *a.shape[1:]), kv_caches)
    gate = params["exit_gate"]

    def one_pass(carry, xs):
        hidden, pool = carry
        t, caches = xs
        with jax.named_scope("loop_pass"):
            hidden, new, aux = transformer_forward(
                cfg, stack, hidden, rope=rope_cache,
                position_ids=position_ids, segment_ids=segment_ids,
                kv_caches=pool if in_pool else caches,
                cache_index=cache_index, paged=paged,
                layer_offset=first_layer, pool_first_layer=-t * L)
        with jax.named_scope("loop_norm_gate"):
            hidden = norm(hidden, params["final_norm"],
                          m.layernorm_epsilon, m.use_rms_norm)
            g = jnp.einsum("bsh,ho->bs", hidden,
                           gate["kernel"].astype(hidden.dtype),
                           preferred_element_type=jnp.float32)
            g = g + gate["bias"].astype(jnp.float32)
        return (hidden, new if in_pool else pool), (
            g, aux, None if in_pool else new)

    (hidden, pool), (gates, aux, caches) = jax.lax.scan(
        one_pass, (hidden, kv_caches if in_pool else None),
        (jnp.arange(T, dtype=jnp.int32), per_pass))
    new_caches = pool if in_pool else jax.tree.map(
        lambda a: a.reshape(T * L, *a.shape[2:]), caches)
    pdf = jnp.moveaxis(exit_pdf(gates), 0, -1)              # [b, s, T]

    def ret(out):
        return ((out, new_caches, (aux.sum(0), pdf)) if return_aux
                else (out, new_caches))

    if not logits_postprocess:
        return ret(hidden)
    with jax.named_scope("lm_head_loss"):
        logits = compute_logits(cfg, params, hidden)
        return ret(logits if labels is None
                   else softmax_cross_entropy(logits, labels))


def _gated(m, key: jax.Array) -> dict:
    """The final norm's low-rank gate (``gated_norm``, ops/norms.py), where
    the model has one; at the file's end so that the lines above stand."""
    return dict(gated_rank=m.gated_norm_rank,
                key=jax.random.fold_in(key, 4)) if m.gated_norm else {}
