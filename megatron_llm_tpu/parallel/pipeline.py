"""Pipeline parallelism: collective-permute microbatch pipelining inside jit.

Replaces the reference's pipeline engine (megatron/schedules.py:606-722 1F1B,
p2p_communication.py isend/irecv) with the TPU-native formulation:

* stage placement is *data placement*: the stacked layer axis [L, ...] is
  sharded over the ``pp`` mesh axis (each stage holds L/pp contiguous layers)
  — no per-stage module classes, and checkpoint resharding over pp is a
  resharding no-op.
* stage transfer is ``lax.ppermute`` over ``pp`` inside a ``lax.scan`` over
  microbatch "ticks" — XLA lowers it to ICI collective-permute, the hardware
  analog of the reference's batched isend/irecv (p2p_communication.py:205-231).
* the schedule: every stage computes each tick; tick t feeds microbatch t into
  stage 0; the last stage emits microbatch t-(pp-1) at tick t. Total ticks
  M + pp - 1 — the same bubble as the reference's warmup(pp-rank-1)/steady/
  cooldown accounting (schedules.py:648-720).
* backward is autodiff through the scan: ppermute transposes to the reverse
  permute, giving the mirrored cooldown. This GPipe-style schedule
  (all-forward-then-all-backward per jit step) coexists with the true 1F1B
  (grads inside the tick loop, O(pp) activations —
  :func:`pipeline_1f1b_loss_and_grads`) and its interleaved variant
  (:func:`pipeline_1f1b_interleaved_loss_and_grads`).
* only ``pp`` is manual (shard_map axis_names={'pp'}): dp/tp/sp shardings
  inside the stage body stay under GSPMD exactly as in the pp=1 path.

Embedding, final norm, and the LM head run outside the pipelined region,
replicated over pp (their grads psum over pp automatically under pjit) —
which also implements the reference's first/last-stage embedding tying
(module.py:52-121) without an explicit embedding group.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.core import rng as rng_mod
from megatron_llm_tpu.parallel import compat
from megatron_llm_tpu.core.parallel_state import CP_AXIS, PP_AXIS
from megatron_llm_tpu.models import language_model as lm
from megatron_llm_tpu.models.transformer import transformer_forward
from megatron_llm_tpu.ops.cross_entropy import (
    chunked_softmax_cross_entropy_from_hidden,
    softmax_cross_entropy,
)
from megatron_llm_tpu.ops.norms import norm


def _stage_body(cfg, layers_local, x, aux, token_idx, dropout_key,
                deterministic, rope, layer_offset=None):
    """Run this stage's local layers on one microbatch of hidden states.

    ``dropout_key`` is the per-microbatch key (the same one the pp=1 path
    hands to transformer_forward, which folds it per *global* layer index) —
    so with cp=1, pipelined dropout is bit-identical to the pp=1 run.

    Returns (hidden, moe_aux[2]) — the stage-local MoE router losses
    (zeros for dense models). The GPipe schedule accumulates them through
    the tick scan; the 1F1B schedules fold them into the per-stage vjp's
    aux output (see _1f1b_setup's aux_scalar).
    """
    stage = jax.lax.axis_index(PP_AXIS)
    if dropout_key is not None and cfg.parallel.context_parallel_size > 1:
        # distinct dropout streams per cp seq-chunk (analog of the reference's
        # per-TP-rank RNG fork inside parallel regions, random.py:144-172)
        dropout_key = jax.random.fold_in(
            dropout_key, jax.lax.axis_index(CP_AXIS)
        )
    layers_per_stage = jax.tree_util.tree_leaves(layers_local)[0].shape[0]
    if layer_offset is None:
        layer_offset = stage * layers_per_stage
    # encoder-decoder stages (models/t5.py:t5_pipeline_loss_fn): the encoder
    # output and the (caller-precomputed) cross-attention bias ride the aux
    # dict to every stage — the engine stays model-agnostic
    encoder_hidden = aux.get("encoder_hidden")
    enc_bias = aux.get("enc_bias")
    hidden, _, moe_aux = transformer_forward(
        cfg, layers_local, x,
        rope=rope,
        position_ids=aux.get("position_ids"),
        segment_ids=aux.get("segment_ids"),
        token_idx=token_idx,
        encoder_hidden=encoder_hidden,
        enc_bias=enc_bias,
        dropout_key=dropout_key,
        deterministic=deterministic,
        layer_offset=layer_offset,
    )
    return hidden, moe_aux


def microbatch_keys(base_key, M: int):
    """Per-microbatch (embed_key, layers_key) pairs, matching the pp=1
    grad-accumulation path exactly: fold_in(base, mb) then split for the
    embedding dropout (model_forward:150-152)."""
    if base_key is None:
        return None, None
    keys = jax.vmap(
        lambda i: jax.random.split(jax.random.fold_in(base_key, i))
    )(jnp.arange(M))
    return keys[:, 0], keys[:, 1]  # [M, keydata] each


def num_pipeline_ticks(M: int, pp: int, v: int) -> int:
    """Tick count of the (interleaved) schedule; v=1 is plain GPipe order.

    Virtual pipelining runs microbatches in groups of pp; a group occupies a
    stage for v*pp consecutive ticks (chunk-major: chunk c of all pp members
    before chunk c+1, ref schedules.py:253-344 model-chunk ordering), and
    each tick does 1/v of a stage's layers — so the pipeline-fill bubble
    shrinks from (pp-1) full-stage ticks to (pp-1) chunk ticks.
    """
    if v == 1:
        return M + pp - 1
    m_pad = -(-M // pp) * pp  # groups are pp-strided; pad the last group
    return m_pad * v + pp - 1


def pipeline_bubble_fraction(M: int, pp: int, v: int = 1) -> float:
    """Idle fraction of the tick schedule: (T - M*v) / T.

    Reference accounting (Megatron SC21 paper; schedules.py warmup/cooldown
    math): bubble = (pp-1)/(M+pp-1) non-interleaved, ~(pp-1)/(M*v+pp-1)
    interleaved."""
    t = num_pipeline_ticks(M, pp, v)
    return (t - M * v) / t


def pipeline_apply(cfg, mesh, stacked_layers, hidden_mb: jax.Array,
                   aux_mb: Dict[str, jax.Array], dropout_key, deterministic,
                   rope, token_idx: Optional[jax.Array] = None,
                   mb_keys: Optional[jax.Array] = None):
    """Run the pipelined transformer body.

    hidden_mb: [M, mb, s, h] embedded microbatches; aux_mb leaves [M, mb, s];
    token_idx: optional [s] zigzag index vector (parallel/ring.py);
    mb_keys: optional [M, ...] per-microbatch dropout keys (microbatch_keys).
    Returns [M, mb, s, h] final hidden states (replicated over pp).

    With cfg.parallel.virtual_pipeline_model_parallel_size = v > 1, each
    stage holds v layer chunks (virtual stage k = c*pp + s holds layers
    [k*L/(v*pp), (k+1)*L/(v*pp))) and a microbatch traverses the stage ring
    v times — the interleaved schedule of ref schedules.py:253-502, which
    cuts the pipeline-fill bubble by v (see pipeline_bubble_fraction).
    """
    pp = cfg.parallel.pipeline_model_parallel_size
    v = cfg.parallel.virtual_pipeline_model_parallel_size or 1
    M = hidden_mb.shape[0]
    L = jax.tree_util.tree_leaves(stacked_layers)[0].shape[0]
    assert L % (pp * v) == 0, (L, pp, v)
    chunk_layers = L // (pp * v)
    T = num_pipeline_ticks(M, pp, v)
    if mb_keys is None and dropout_key is not None and not deterministic:
        # direct callers passing only dropout_key get the per-microbatch
        # derivation (the keys pipeline_loss_fn would have passed)
        _, mb_keys = microbatch_keys(dropout_key, M)
    use_dropout = mb_keys is not None and not deterministic

    if token_idx is None:
        # constant placeholder so the shard_map signature is static; the
        # sentinel -1 row is never read (selected below)
        token_idx_arr = jnp.full((hidden_mb.shape[2],), -1, jnp.int32)
    else:
        token_idx_arr = token_idx
    if mb_keys is None:
        mb_keys = jnp.zeros((M, 2), jnp.uint32)  # static-signature dummy

    # [L, ...] -> [v, pp, Lc, ...]: axis 1 shards over pp, so stage s locally
    # holds [v, Lc, ...] = chunks {c*pp + s}. For v=1 this is the old
    # contiguous L/pp split.
    def chunked(a):
        return a.reshape(v, pp, chunk_layers, *a.shape[1:])

    layers_chunked = jax.tree.map(chunked, stacked_layers)

    def body(layers_local, hidden_mb, aux_mb, token_idx_local, mb_keys_local):
        stage = jax.lax.axis_index(PP_AXIS)
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        layers_local = jax.tree.map(lambda a: a[:, 0], layers_local)  # [v, Lc, ...]

        def tick(carry, t):
            recv, out_buf, aux_acc = carry
            # schedule position: stage s at tick t serves chain position
            # u = t - s; groups of pp microbatches, chunk-major within group
            u = t - stage
            w = u % (v * pp)
            c = jnp.clip(w // pp, 0, v - 1)
            mbi = (u // (v * pp)) * pp + w % pp
            valid = jnp.logical_and(u >= 0, mbi < M)
            mb_idx = jnp.clip(mbi, 0, M - 1)

            x_in = jax.tree.map(lambda a: a[mb_idx], hidden_mb)
            aux = jax.tree.map(lambda a: a[mb_idx], aux_mb)
            first_hop = jnp.logical_and(stage == 0, c == 0)
            inp = jnp.where(first_hop, x_in, recv)
            dk = mb_keys_local[mb_idx] if use_dropout else None
            chunk_params = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                layers_local,
            )
            out, moe_aux = _stage_body(
                cfg, chunk_params, inp, aux,
                token_idx_local if token_idx is not None else None,
                dk, deterministic, rope,
                layer_offset=(c * pp + stage) * chunk_layers,
            )
            # each (stage, chunk) serves a valid microbatch exactly once, so
            # gating on `valid` counts every layer's router loss once
            aux_acc = aux_acc + jnp.where(valid, moe_aux, 0.0)
            # final output for this microbatch leaves from the last virtual
            # stage (stage pp-1, chunk v-1)
            emit = jnp.logical_and(
                jnp.logical_and(stage == pp - 1, c == v - 1), valid
            )
            prev = jax.lax.dynamic_index_in_dim(out_buf, mb_idx, 0,
                                                keepdims=False)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(emit, out, prev), mb_idx, 0
            )
            nxt = jax.lax.ppermute(out, PP_AXIS, perm)
            return (nxt, out_buf, aux_acc), None

        from megatron_llm_tpu.models.moe import zero_aux

        init = (jnp.zeros_like(hidden_mb[0]), jnp.zeros_like(hidden_mb),
                zero_aux())
        (_, out_buf, aux_acc), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # broadcast last-stage results to every stage (psum of one-hot data);
        # transpose of this psum routes dLoss back to the last stage only.
        # MoE router losses: each stage holds its own layers' sum -> psum
        # over pp gives the all-layer total (differentiable: the GPipe
        # backward carries d(aux)/d(router) through the scan transpose).
        return jax.lax.psum(out_buf, PP_AXIS), jax.lax.psum(aux_acc, PP_AXIS)

    # cp joins pp as a manual axis: hidden/aux seq dims are cp-local inside
    # the body, and the attention dispatch takes the ring_attention_manual
    # path (parallel/ring.py) — one shard_map, no nesting.
    P = jax.sharding.PartitionSpec
    hidden_spec = P(None, None, CP_AXIS, None)  # [M, mb, s, h]
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(None, PP_AXIS), layers_chunked),
            hidden_spec,
            _aux_specs(aux_mb),
            P(CP_AXIS),
            P(),
        ),
        out_specs=(hidden_spec, P()),
        axis_names={PP_AXIS, CP_AXIS},
        check_vma=False,
    )
    return fn(layers_chunked, hidden_mb, aux_mb, token_idx_arr, mb_keys)


# ---------------------------------------------------------------------------
# True 1F1B: gradients computed inside the tick loop, O(pp) activation memory
# ---------------------------------------------------------------------------


def _aux_data_spec(leaf):
    """shard_map in-spec for one [M, mb, ...] aux leaf: the seq axis (dim 2)
    shards over cp; per-sample leaves (e.g. BERT is_random [M, mb]) replicate."""
    P = jax.sharding.PartitionSpec
    if leaf.ndim >= 3:
        return P(None, None, CP_AXIS)
    return P(*([None] * leaf.ndim))


def _aux_specs(aux_mb):
    """Key-aware aux specs: cross-attention KEYS stay replicated over cp —
    every cp-local decoder query chunk attends the FULL encoder sequence
    (models/t5.py), so sharding encoder_hidden/enc_bias over cp would
    silently truncate cross-attention to 1/cp of the keys."""
    P = jax.sharding.PartitionSpec
    return {
        k: (P() if k in ("encoder_hidden", "enc_bias")
            else _aux_data_spec(v))
        for k, v in aux_mb.items()
    }


def microbatched_head_loss(head_loss_fn, outer, hidden, labels, loss_mask,
                           aux_mb):
    """Sum per-microbatch head-loss contributions over [M, ...] arrays.

    One microbatch at a time: materializing [M, mb, s, v] logits for the
    whole global batch (vocab 32k, seq 4k, M=16 -> tens of GB) would defeat
    microbatching; the remat keeps the scan VJP from saving each
    iteration's logits as residuals (the same footprint again). Shared by
    pipeline_loss_fn and family-owned pipelines (models/t5.py).
    """

    @functools.partial(jax.checkpoint, policy=None)
    def head_mb(hid, lbl, msk, i):
        aux = jax.tree.map(lambda a: a[i], aux_mb)
        return head_loss_fn(outer, hid, lbl, msk, aux)

    def acc_mb(loss_sum, inp):
        hid, lbl, msk, i = inp
        return loss_sum + head_mb(hid, lbl, msk, i), None

    loss, _ = jax.lax.scan(
        acc_mb, jnp.float32(0.0),
        (hidden, labels, loss_mask, jnp.arange(hidden.shape[0])),
    )
    return loss


def _split_extra_keys(batch, split):
    """Microbatch-split every batch key outside the engine's positional
    tokens/labels/loss_mask/token_idx contract — they reach the stage body
    (segment_ids gates attention) and the embed/head hooks as ``aux``."""
    return {
        k: split(v) for k, v in batch.items()
        if k not in ("tokens", "labels", "loss_mask", "token_idx")
        and v is not None
    }


def _default_gpt_fns(cfg, batch, use_dropout):
    """Default GPT-family hooks shared by every schedule: embedding (+optional
    dropout) and final-norm + LM head + globally-normalized masked CE.
    head_loss_fn returns the UNSCALED per-microbatch contribution."""
    denom = jnp.maximum(batch["loss_mask"].astype(jnp.float32).sum(), 1.0)

    def embed_fn(outer_p, tok, aux, ke):
        h = lm.embed_tokens(cfg, outer_p, tok, aux.get("position_ids"))
        if use_dropout and ke is not None:
            h = rng_mod.dropout(ke, cfg.model.hidden_dropout, h)
        return h

    def head_loss_fn(outer_p, hidden, lbl, msk, aux):
        h = norm(hidden, outer_p["final_norm"], cfg.model.layernorm_epsilon,
                 cfg.model.use_rms_norm)
        if cfg.model.ce_vocab_chunks:
            # same vocab-chunked head fusion as the pp=1 path (model_forward)
            per_token = chunked_softmax_cross_entropy_from_hidden(
                h, lm.head_weight(cfg, outer_p).astype(h.dtype), lbl,
                cfg.model.ce_vocab_chunks,
            )
        else:
            logits = lm.compute_logits(cfg, outer_p, h)
            per_token = softmax_cross_entropy(logits, lbl)
        return (per_token * msk.astype(jnp.float32)).sum() / denom

    return embed_fn, head_loss_fn


def _1f1b_setup(cfg, batch, num_micro, dropout_key, embed_fn, head_loss_fn,
                loss_scale, rope):
    """Shared preamble of both 1F1B schedules: microbatch splits, dropout
    keys, params split, compute dtype, and the default GPT embed/head fns.

    ``head_loss_fn(outer_p, hidden, labels, mask, aux)`` returns the
    UNSCALED loss contribution of one microbatch (normalizers are closures
    over the full batch); the engine applies the fp16 loss scale. Custom
    families (e.g. BERT, models/bert.py:bert_pipeline_hooks) override both
    fns; every batch key other than tokens/labels/loss_mask/token_idx is
    microbatch-split into ``aux`` and reaches both hooks and the stage body
    (where ``segment_ids`` gates attention).
    """
    M = num_micro or cfg.parallel.num_micro_batches or 1
    gbs = batch["tokens"].shape[0]
    assert gbs % M == 0
    s = {"M": M, "mb": gbs // M}
    s["rope"] = rope if rope is not None else lm.make_rope_cache(cfg)
    s["scale"] = loss_scale if loss_scale is not None else jnp.float32(1.0)

    def split(x):
        return x.reshape(M, gbs // M, *x.shape[1:])

    s["tokens"] = split(batch["tokens"])
    s["labels"] = split(batch["labels"])
    s["loss_mask"] = split(batch["loss_mask"]).astype(jnp.float32)
    s["aux_mb"] = _split_extra_keys(batch, split)
    s["token_idx"] = batch.get("token_idx")
    s["denom"] = jnp.maximum(s["loss_mask"].sum(), 1.0)
    s["dtype"] = (
        jnp.bfloat16 if cfg.training.params_dtype == "bfloat16"
        else jnp.float16 if cfg.training.params_dtype == "float16"
        else jnp.float32
    )

    use_dropout = (
        dropout_key is not None
        and (cfg.model.hidden_dropout > 0.0 or cfg.model.attention_dropout > 0.0)
    )
    s["use_dropout"] = use_dropout
    embed_keys, layer_keys = microbatch_keys(
        dropout_key if use_dropout else None, M
    )
    if embed_keys is None:  # static shard_map signature
        embed_keys = jnp.zeros((M, 2), jnp.uint32)
        layer_keys = jnp.zeros((M, 2), jnp.uint32)
    s["embed_keys"], s["layer_keys"] = embed_keys, layer_keys

    # pp-vocab-parallel head (cfg.parallel.pp_vocab_parallel_head): in
    # lockstep SPMD a "last-stage-only" head is structurally impossible —
    # every stage executes every tick — so instead of pp-1 stages computing
    # a masked-out FULL head, the vocab is sharded over pp and every stage
    # computes a USEFUL 1/pp of it (logits chunk + the 3-psum
    # vocab-parallel CE over the pp axis; ops/cross_entropy.py). Only for
    # the default GPT head; the padded vocab must divide pp.
    pp_ = cfg.parallel.pipeline_model_parallel_size
    s["pp_head"] = (
        cfg.parallel.pp_vocab_parallel_head
        and head_loss_fn is None
        and pp_ > 1
        and lm.padded_vocab_size(cfg.model.vocab_size, cfg) % pp_ == 0
        # an explicit ce_vocab_chunks bound wins: the pp head materializes
        # an unchunked [mb, s, V/pp] logits block, which can exceed the
        # memory budget chunking was configured to enforce — keep the
        # replicated chunked head (which _default_gpt_fns honors) instead
        and not cfg.model.ce_vocab_chunks
    )
    if s["pp_head"]:
        from megatron_llm_tpu.ops.cross_entropy import (
            vocab_parallel_cross_entropy,
        )

        denom_ = s["denom"]
        scale_ = s["scale"]

        def pp_head_loss_fn(outer_p, hidden, lbl, msk, aux):
            """SCALED per-microbatch loss from this stage's vocab chunk.

            ``hidden`` is the last stage's output broadcast to every stage
            (psum of a one-hot selection); the psums inside the
            vocab-parallel CE make the returned value identical on every
            stage — the caller counts it once and psums the partial
            weight/hidden grads."""
            h = norm(hidden, outer_p["final_norm"],
                     cfg.model.layernorm_epsilon, cfg.model.use_rms_norm)
            w = lm.head_weight(cfg, outer_p).astype(h.dtype)
            vc = w.shape[1] // pp_
            rank = jax.lax.axis_index(PP_AXIS)
            wc = jax.lax.dynamic_slice_in_dim(w, rank * vc, vc, axis=1)
            per_token = vocab_parallel_cross_entropy(
                h @ wc, lbl, axis_name=PP_AXIS)
            return ((per_token * msk.astype(jnp.float32)).sum()
                    / denom_ * scale_)

        s["pp_head_loss_fn"] = pp_head_loss_fn

    default_embed, default_head = _default_gpt_fns(cfg, batch, use_dropout)
    if embed_fn is None:
        embed_fn = default_embed
    if head_loss_fn is None:
        head_loss_fn = default_head

    # the engine owns the fp16 loss scale so hooks stay scale-agnostic
    scale = s["scale"]
    unscaled = head_loss_fn

    def scaled_head(outer_p, hidden, lbl, msk, aux):
        return unscaled(outer_p, hidden, lbl, msk, aux) * scale

    s["embed_fn"], s["head_loss_fn"] = embed_fn, scaled_head
    s["token_idx_arr"] = (
        jnp.full((s["tokens"].shape[2],), -1, jnp.int32)
        if s["token_idx"] is None else s["token_idx"]
    )

    # MoE router aux losses under 1F1B: the aux term is stage-LOCAL (each
    # stage's routers see only that stage's layers), so its gradient never
    # crosses stage boundaries through dy — seeding the aux output of the
    # per-stage vjp with the loss scale at the stage's own backward tick
    # recovers exactly the gradient GPipe gets through the scan transpose.
    # The /M matches the pp=1 grad-accum mean (pipeline_loss_fn does the
    # same division).
    s["has_moe"] = cfg.model.num_experts is not None
    if s["has_moe"]:
        from megatron_llm_tpu.models.moe import aux_loss_coeffs

        c_bal, c_z = aux_loss_coeffs(cfg)
        M_ = s["M"]

        def aux_scalar(moe_aux):
            return (c_bal * moe_aux[0] + c_z * moe_aux[1]) / M_
    else:
        def aux_scalar(moe_aux):
            del moe_aux
            return jnp.float32(0.0)
    s["aux_scalar"] = aux_scalar
    return s


def _pp_head_tick(st, pp, outer_p, y, labels, loss_mask, aux_at,
                  use_head, emitted, e_idx, loss_acc, acc_outer):
    """Shared pp-vocab-head step of the 1F1B ticks (both engines).

    Broadcasts the emitting stage's output, runs THIS stage's vocab-chunk
    head vjp, and returns the updated (loss_acc, acc_outer, dy_total).
    ``emitted``/``e_idx`` are tick-derived and identical on every stage
    (each engine computes them from its own schedule); ``use_head`` is the
    emitting stage's own flag. vjp seed is 1/pp: inside shard_map a
    replicated cotangent of 1.0 per rank counts pp times through the CE's
    internal psums (verified with a 2-rank psum-vjp probe that returned
    2x the chunk partials); 1/pp makes each rank's vjp the clean chunk
    partial, which the psums assemble.
    """
    y_b = jax.lax.psum(
        jnp.where(use_head, y, jnp.zeros_like(y)), PP_AXIS)
    loss_f, head_vjp = jax.vjp(
        lambda op, yy: st["pp_head_loss_fn"](
            op, yy, labels[e_idx], loss_mask[e_idx], aux_at(e_idx)),
        outer_p, y_b,
    )
    d_outer_head, dy_p = head_vjp(jnp.float32(1.0 / pp))
    # loss_f is already the GLOBAL value on every stage (CE psums
    # internally) — count it once (the emitting stage)
    loss_acc = loss_acc + jnp.where(use_head, loss_f, 0.0)
    acc_outer = jax.tree.map(
        lambda a, g: a + jnp.where(emitted, g, jnp.zeros_like(g)),
        acc_outer, d_outer_head,
    )
    return loss_acc, acc_outer, jax.lax.psum(dy_p, PP_AXIS)


def _1f1b_metrics(st, loss_ce, aux_tot):
    """Reporting dict for the 1F1B engines (``with_metrics=True``): bare CE
    as "lm loss" — matching loss_from_batch / pipeline_loss_fn, so the
    metric means the same thing under every schedule — plus the combined
    coeff-weighted router aux for MoE. Values are UNSCALED (the engine's
    accumulators carry the fp16 loss scale; the train step's convention is
    raw metrics, training_step.py:136)."""
    inv = 1.0 / st["scale"]
    mets = {"lm loss": loss_ce * inv}
    if st["has_moe"]:
        mets["moe aux total"] = aux_tot * inv
    return mets


def pipeline_1f1b_loss_and_grads(
    cfg, mesh, params, batch: Dict[str, jax.Array], *,
    rope=None, loss_scale=None, num_micro=None, dropout_key=None,
    embed_fn=None, head_loss_fn=None, with_metrics=False,
):
    """One-forward-one-backward pipeline schedule (schedules.py:606-722).

    Unlike :func:`pipeline_loss_fn` (GPipe-style: autodiff through the tick
    scan, which saves one stage-input per tick — O(M) activation memory),
    this computes gradients INSIDE the loop: at tick t, stage s runs the
    forward for microbatch ``t - s`` and the backward (via ``jax.vjp`` on the
    saved stage input — rematerialized, the recompute analog of the
    reference's activation checkpointing) for microbatch ``t - 2(pp-1) + s``.
    Saved inputs live in a ring buffer of depth 2*pp — the O(pp) in-flight
    memory discipline the reference gets from deallocate_output_tensor +
    1F1B ordering (schedules.py:36-88,648-720).

    The embedding, final norm, LM head and loss run inside the loop on their
    owning stages (first/last); every stage computes them SPMD-style and the
    unused results are masked — the head matmul on non-final stages is the
    price of lockstep SPMD (~h*v/(12*h^2*L/pp) of a tick, a few percent).

    Dropout: per-microbatch keys (``microbatch_keys``) make the vjp-recompute
    reproduce the forward's dropout exactly — the jax analog of the
    reference's RNG-state snapshot around activation recompute
    (random.py:175-245). Pass ``dropout_key`` to enable.

    Custom model families can override ``embed_fn(outer_params, tokens, aux,
    key)`` and ``head_loss_fn(outer_params, hidden, labels, mask, aux) ->
    UNSCALED per-microbatch loss contribution`` — the engine applies the
    fp16 loss scale itself; normalizers should be closures over the full
    batch (defaults implement the GPT/Llama family; BERT:
    models/bert.py:bert_pipeline_hooks).

    Returns (loss, grads) with grads matching the params tree.
    """
    assert (cfg.parallel.virtual_pipeline_model_parallel_size or 1) == 1, (
        "this is the non-interleaved schedule; with "
        "virtual_pipeline_model_parallel_size > 1 use "
        "pipeline_1f1b_interleaved_loss_and_grads"
    )
    pp = cfg.parallel.pipeline_model_parallel_size
    st = _1f1b_setup(cfg, batch, num_micro, dropout_key, embed_fn,
                     head_loss_fn, loss_scale, rope)
    M, mb = st["M"], st["mb"]
    rope = st["rope"]
    tokens, labels, loss_mask = st["tokens"], st["labels"], st["loss_mask"]
    aux_mb, token_idx = st["aux_mb"], st["token_idx"]
    use_dropout = st["use_dropout"]
    embed_keys, layer_keys = st["embed_keys"], st["layer_keys"]
    embed_fn, head_loss_fn = st["embed_fn"], st["head_loss_fn"]

    # params split: layers are pp-sharded; everything else ("outer": embedding,
    # final_norm, lm_head if untied) is replicated and used at the ends.
    layers = params["layers"]
    outer = {k: v for k, v in params.items() if k != "layers"}

    def body(layers_local, outer_p, tokens, labels, loss_mask, aux_mb,
             token_idx_local, embed_keys, layer_keys):
        stage = jax.lax.axis_index(PP_AXIS)
        last = pp - 1
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]
        depth = 2 * pp
        s_local = tokens.shape[2]
        h = cfg.model.hidden_size
        dtype = st["dtype"]

        def stage_fwd(L, x, aux, dk):
            y, moe_aux = _stage_body(
                cfg, L, x, aux,
                token_idx_local if token_idx is not None else None,
                dk if use_dropout else None, not use_dropout, rope,
            )
            # (hidden, stage-local scaled-down aux loss); the aux output's
            # vjp seed at the backward tick carries the router gradient
            return y, st["aux_scalar"](moe_aux)

        def aux_at(i):
            return jax.tree.map(lambda a: a[i], aux_mb)

        def tick(carry, t):
            x_recv, g_recv, saved, acc_L, acc_outer, loss_acc, aux_acc = carry
            f_mb = t - stage
            b_mb = t - 2 * (pp - 1) + stage
            do_f = jnp.logical_and(f_mb >= 0, f_mb < M)
            do_b = jnp.logical_and(b_mb >= 0, b_mb < M)
            f_idx = jnp.clip(f_mb, 0, M - 1)
            b_idx = jnp.clip(b_mb, 0, M - 1)

            # ---- forward: embed on stage 0, else the ppermuted stream ----
            x_emb = embed_fn(outer_p, tokens[f_idx], aux_at(f_idx),
                             embed_keys[f_idx] if use_dropout else None)
            x_in = jnp.where(stage == 0, x_emb, x_recv).astype(dtype)
            # guard the save: during cooldown f_idx clips to M-1, whose slot
            # may still be awaiting its backward
            saved_upd = jax.lax.dynamic_update_index_in_dim(
                saved, x_in, f_idx % depth, 0
            )
            saved = jnp.where(do_f, saved_upd, saved)
            y, aux_f = stage_fwd(layers_local, x_in, aux_at(f_idx),
                                 layer_keys[f_idx])
            # every stage adds its own (already /M) router aux once per
            # valid microbatch — into the SEPARATE aux accumulator so the
            # reported "lm loss" is bare CE like every other path's
            # (aux_acc psums over pp below and rejoins the total loss)
            aux_acc = aux_acc + jnp.where(do_f, aux_f * st["scale"], 0.0)

            # ---- head + loss on the last stage's fresh output ----
            use_head = jnp.logical_and(stage == last, do_f)
            if st["pp_head"]:
                # pp-vocab head (_pp_head_tick): every stage computes its
                # vocab chunk's partial CE + grads (USEFUL work, 1/pp of
                # the head each). emitted/e_idx are tick-derived — the
                # EMITTED microbatch, identical on all stages (f_idx is
                # stage-specific and differs on non-last stages)
                emitted = jnp.logical_and(t - last >= 0, t - last < M)
                e_idx = jnp.clip(t - last, 0, M - 1)
                loss_acc, acc_outer, dy = _pp_head_tick(
                    st, pp, outer_p, y, labels, loss_mask, aux_at,
                    use_head, emitted, e_idx, loss_acc, acc_outer)
            else:
                loss_f, head_vjp = jax.vjp(
                    lambda op, yy: head_loss_fn(op, yy, labels[f_idx],
                                                loss_mask[f_idx],
                                                aux_at(f_idx)),
                    outer_p, y,
                )
                d_outer_head, dy = head_vjp(jnp.float32(1.0))
                loss_acc = loss_acc + jnp.where(use_head, loss_f, 0.0)
                acc_outer = jax.tree.map(
                    lambda a, g: a + jnp.where(use_head, g,
                                               jnp.zeros_like(g)),
                    acc_outer, d_outer_head,
                )

            # ---- backward for the older microbatch (remat from saved x) ----
            g_in = jnp.where(stage == last, dy.astype(dtype), g_recv)
            x_saved = jax.lax.dynamic_index_in_dim(
                saved, b_idx % depth, 0, keepdims=False
            )
            _, stage_vjp = jax.vjp(
                lambda L, xx: stage_fwd(L, xx, aux_at(b_idx),
                                        layer_keys[b_idx]),
                layers_local, x_saved,
            )
            # aux cotangent = loss scale: the router-aux gradient enters
            # here (stage-local); for dense models the aux output is a
            # constant 0 and the seed is a no-op
            dlayers, dx = stage_vjp((g_in, st["scale"]))
            acc_L = jax.tree.map(
                lambda a, g: a + jnp.where(do_b, g, jnp.zeros_like(g)),
                acc_L, dlayers,
            )

            # ---- embedding backward on stage 0 ----
            _, emb_vjp = jax.vjp(
                lambda op: embed_fn(op, tokens[b_idx], aux_at(b_idx),
                                    embed_keys[b_idx] if use_dropout else None),
                outer_p,
            )
            (d_outer_emb,) = emb_vjp(dx)
            use_emb = jnp.logical_and(stage == 0, do_b)
            acc_outer = jax.tree.map(
                lambda a, g: a + jnp.where(use_emb, g, jnp.zeros_like(g)),
                acc_outer, d_outer_emb,
            )

            x_next = jax.lax.ppermute(y.astype(dtype), PP_AXIS, perm_fwd)
            g_next = jax.lax.ppermute(dx, PP_AXIS, perm_bwd)
            return (x_next, g_next, saved, acc_L, acc_outer, loss_acc,
                    aux_acc), None

        zero_x = jnp.zeros((mb, s_local, h), dtype)
        init = (
            zero_x,
            zero_x,
            jnp.zeros((depth, mb, s_local, h), dtype),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         layers_local),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), outer_p),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        (_, _, _, acc_L, acc_outer, loss_acc, aux_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(M + 2 * (pp - 1))
        )
        # cp shards contribute partial sums over their seq chunks; pp stages
        # hold zeros for params they do not own (outer) — psum both.
        acc_L = jax.lax.psum(acc_L, CP_AXIS)
        acc_outer = jax.lax.psum(
            jax.lax.psum(acc_outer, PP_AXIS), CP_AXIS
        )
        loss_acc = jax.lax.psum(jax.lax.psum(loss_acc, PP_AXIS), CP_AXIS)
        aux_acc = jax.lax.psum(jax.lax.psum(aux_acc, PP_AXIS), CP_AXIS)
        return acc_L, acc_outer, loss_acc, aux_acc

    P = jax.sharding.PartitionSpec
    data_spec = P(None, None, CP_AXIS)
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(PP_AXIS), layers),
            jax.tree.map(lambda _: P(), outer),
            data_spec, data_spec, data_spec,
            _aux_specs(aux_mb),
            P(CP_AXIS),
            P(), P(),
        ),
        out_specs=(
            jax.tree.map(lambda _: P(PP_AXIS), layers),
            jax.tree.map(lambda _: P(), outer),
            P(), P(),
        ),
        axis_names={PP_AXIS, CP_AXIS},
        check_vma=False,
    )
    grads_L, grads_outer, loss_ce, aux_tot = fn(
        layers, outer, tokens, labels, loss_mask, aux_mb, st["token_idx_arr"],
        embed_keys, layer_keys,
    )
    grads = dict(grads_outer)
    grads["layers"] = grads_L
    loss = loss_ce + aux_tot
    if with_metrics:
        return loss, grads, _1f1b_metrics(st, loss_ce, aux_tot)
    return loss, grads


def pipeline_1f1b_interleaved_loss_and_grads(
    cfg, mesh, params, batch: Dict[str, jax.Array], *,
    rope=None, loss_scale=None, num_micro=None, dropout_key=None,
    embed_fn=None, head_loss_fn=None, with_metrics=False,
):
    """Interleaved (virtual-pipeline) 1F1B: grads inside the tick loop with
    v layer chunks per stage (reference schedules.py:253-502 +
    parallel_state.py:406-421 virtual ranks).

    Schedule: virtual stage k = c*pp + s; V = v*pp hops per microbatch.
    Microbatches run in pp-sized groups, chunk-major (the same forward
    mapping as the interleaved gpipe schedule in :func:`pipeline_apply`);
    the backward is its time-shifted mirror — at tick t stage s runs
      forward  of chain position u = t - s          (chunk u%(v*pp)//pp),
      backward of chain position j ≡ (V-1-s) mod pp (virtual stage V-1-j),
    one fwd and one bwd chunk-step per stage per tick, so the pipeline-fill
    bubble shrinks by v while in-flight activations stay O(V) (ring buffer
    of depth 2V+2pp saved chunk inputs) instead of the gpipe autodiff's
    O(M*v) tick residuals.

    The last stage's head vjp runs at the microbatch's final forward tick;
    dy is held one tick in a depth-pp ring until its backward starts.

    Lockstep cost note: as in the non-interleaved 1F1B, every stage computes
    the (masked-out) head and embedding vjps every tick. Each interleaved
    tick does only 1/v of a stage's layers, so that fixed overhead is ~v x
    larger relative to useful work than non-interleaved — with a very large
    vocab and few layers per chunk, prefer smaller v (or the gpipe schedule,
    whose head runs outside the pipelined region).
    """
    pp = cfg.parallel.pipeline_model_parallel_size
    v = cfg.parallel.virtual_pipeline_model_parallel_size or 1
    V = v * pp
    st = _1f1b_setup(cfg, batch, num_micro, dropout_key, embed_fn,
                     head_loss_fn, loss_scale, rope)
    M, mb = st["M"], st["mb"]
    rope = st["rope"]
    tokens, labels, loss_mask = st["tokens"], st["labels"], st["loss_mask"]
    aux_mb, token_idx = st["aux_mb"], st["token_idx"]
    use_dropout = st["use_dropout"]
    embed_keys, layer_keys = st["embed_keys"], st["layer_keys"]
    embed_fn, head_loss_fn = st["embed_fn"], st["head_loss_fn"]
    m_groups = -(-M // pp)
    T = (m_groups - 1) * v * pp + (pp - 1) + 2 * V
    depth = 2 * V + 2 * pp

    layers = params["layers"]
    outer = {k: x for k, x in params.items() if k != "layers"}
    L = jax.tree_util.tree_leaves(layers)[0].shape[0]
    assert L % V == 0, (L, pp, v)
    chunk_layers = L // V

    def chunked(a):
        return a.reshape(v, pp, chunk_layers, *a.shape[1:])

    layers_chunked = jax.tree.map(chunked, layers)

    def body(layers_local, outer_p, tokens, labels, loss_mask, aux_mb,
             token_idx_local, embed_keys, layer_keys):
        stage = jax.lax.axis_index(PP_AXIS)
        last = pp - 1
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]
        layers_local = jax.tree.map(lambda a: a[:, 0], layers_local)  # [v, Lc]
        s_local = tokens.shape[2]
        h = cfg.model.hidden_size
        dtype = st["dtype"]

        def chunk_at(c):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                layers_local,
            )

        def stage_fwd(ch_params, x, aux, dk, layer_offset):
            y, moe_aux = _stage_body(
                cfg, ch_params, x, aux,
                token_idx_local if token_idx is not None else None,
                dk if use_dropout else None, not use_dropout, rope,
                layer_offset=layer_offset,
            )
            return y, st["aux_scalar"](moe_aux)

        def aux_at(i):
            return jax.tree.map(lambda a: a[i], aux_mb)

        def add_chunk(acc, g, c, valid):
            def upd(a, gg):
                prev = jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False)
                new = prev + jnp.where(valid, gg, jnp.zeros_like(gg))
                return jax.lax.dynamic_update_index_in_dim(a, new, c, 0)

            return jax.tree.map(upd, acc, g)

        def tick(carry, t):
            (x_recv, g_recv, saved, dybuf, acc_L, acc_outer, loss_acc,
             aux_acc) = carry

            # ---- forward mapping (shared with the gpipe interleaved path) --
            u = t - stage
            w = u % V
            c_f = jnp.clip(w // pp, 0, v - 1)
            f_mb_raw = (u // V) * pp + w % pp
            do_f = jnp.logical_and(u >= 0, f_mb_raw < M)
            f_idx = jnp.clip(f_mb_raw, 0, M - 1)
            first_hop = jnp.logical_and(stage == 0, c_f == 0)
            last_hop = jnp.logical_and(stage == last, c_f == v - 1)

            x_emb = embed_fn(outer_p, tokens[f_idx], aux_at(f_idx),
                             embed_keys[f_idx] if use_dropout else None)
            x_in = jnp.where(first_hop, x_emb, x_recv).astype(dtype)
            slot_f = jnp.where(do_f, u % depth, depth - 1)
            saved_upd = jax.lax.dynamic_update_index_in_dim(
                saved, x_in, slot_f, 0
            )
            saved = jnp.where(do_f, saved_upd, saved)
            y, aux_f = stage_fwd(chunk_at(c_f), x_in, aux_at(f_idx),
                                 layer_keys[f_idx],
                                 (c_f * pp + stage) * chunk_layers)
            # each (stage, chunk) hop adds its own (already /M) router aux
            # once per valid microbatch into the SEPARATE aux accumulator
            # (bare-CE reporting, see _1f1b_metrics); psum over pp totals
            # the layers
            aux_acc = aux_acc + jnp.where(do_f, aux_f * st["scale"], 0.0)

            # ---- head vjp at the final forward hop; dy parked one tick ----
            use_head = jnp.logical_and(last_hop, do_f)
            if st["pp_head"]:
                # pp-vocab head (_pp_head_tick); the emission condition of
                # the LAST stage's final hop, derived from t alone so it is
                # identical on every stage
                u_l = t - last
                w_l = u_l % V
                mb_l = (u_l // V) * pp + w_l % pp
                emitted = jnp.logical_and(
                    jnp.logical_and(u_l >= 0, w_l // pp == v - 1), mb_l < M)
                e_idx = jnp.clip(mb_l, 0, M - 1)
                loss_acc, acc_outer, dy = _pp_head_tick(
                    st, pp, outer_p, y, labels, loss_mask, aux_at,
                    use_head, emitted, e_idx, loss_acc, acc_outer)
            else:
                loss_f, head_vjp = jax.vjp(
                    lambda op, yy: head_loss_fn(op, yy, labels[f_idx],
                                                loss_mask[f_idx],
                                                aux_at(f_idx)),
                    outer_p, y,
                )
                d_outer_head, dy = head_vjp(jnp.float32(1.0))
                loss_acc = loss_acc + jnp.where(use_head, loss_f, 0.0)
                acc_outer = jax.tree.map(
                    lambda a, g: a + jnp.where(use_head, g,
                                               jnp.zeros_like(g)),
                    acc_outer, d_outer_head,
                )
            dy_prev = jax.lax.dynamic_index_in_dim(
                dybuf, f_idx % pp, 0, keepdims=False)
            dybuf = jax.lax.dynamic_update_index_in_dim(
                dybuf, jnp.where(use_head, dy.astype(dtype), dy_prev),
                f_idx % pp, 0,
            )

            # ---- backward mapping: j = (V-1-s) % pp + pp*a ----
            base = (V - 1 - stage) % pp
            z = t - V - base
            w2 = z % V
            a2 = w2 // pp
            b_mb_raw = (z // V) * pp + w2 % pp
            j = base + pp * a2
            k_b = V - 1 - j
            c_b = jnp.clip(k_b // pp, 0, v - 1)
            do_b = jnp.logical_and(z >= 0, b_mb_raw < M)
            b_idx = jnp.clip(b_mb_raw, 0, M - 1)
            bwd_first = j == 0            # head's dy enters here
            bwd_last = k_b == 0           # embedding vjp leaves here

            dy_in = jax.lax.dynamic_index_in_dim(
                dybuf, b_idx % pp, 0, keepdims=False)
            g_in = jnp.where(bwd_first, dy_in, g_recv)
            slot_b = ((b_idx // pp) * V + b_idx % pp + c_b * pp) % depth
            x_saved = jax.lax.dynamic_index_in_dim(saved, slot_b, 0,
                                                   keepdims=False)
            _, stage_vjp = jax.vjp(
                lambda ch, xx: stage_fwd(ch, xx, aux_at(b_idx),
                                         layer_keys[b_idx],
                                         (c_b * pp + stage) * chunk_layers),
                chunk_at(c_b), x_saved,
            )
            # aux cotangent = loss scale (router grads; no-op for dense)
            dchunk, dx = stage_vjp((g_in, st["scale"]))
            acc_L = add_chunk(acc_L, dchunk, c_b, do_b)

            # ---- embedding backward at the last backward hop ----
            _, emb_vjp = jax.vjp(
                lambda op: embed_fn(op, tokens[b_idx], aux_at(b_idx),
                                    embed_keys[b_idx] if use_dropout else None),
                outer_p,
            )
            (d_outer_emb,) = emb_vjp(dx)
            use_emb = jnp.logical_and(bwd_last, do_b)
            acc_outer = jax.tree.map(
                lambda a, g: a + jnp.where(use_emb, g, jnp.zeros_like(g)),
                acc_outer, d_outer_emb,
            )

            x_next = jax.lax.ppermute(y.astype(dtype), PP_AXIS, perm_fwd)
            g_next = jax.lax.ppermute(dx.astype(dtype), PP_AXIS, perm_bwd)
            return (x_next, g_next, saved, dybuf, acc_L, acc_outer,
                    loss_acc, aux_acc), None

        zero_x = jnp.zeros((mb, s_local, h), dtype)
        init = (
            zero_x,
            zero_x,
            jnp.zeros((depth, mb, s_local, h), dtype),
            jnp.zeros((pp, mb, s_local, h), dtype),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         layers_local),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), outer_p),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        (_, _, _, _, acc_L, acc_outer, loss_acc, aux_acc), _ = jax.lax.scan(
            tick, init, jnp.arange(T)
        )
        acc_L = jax.lax.psum(acc_L, CP_AXIS)
        acc_outer = jax.lax.psum(jax.lax.psum(acc_outer, PP_AXIS), CP_AXIS)
        loss_acc = jax.lax.psum(jax.lax.psum(loss_acc, PP_AXIS), CP_AXIS)
        aux_acc = jax.lax.psum(jax.lax.psum(aux_acc, PP_AXIS), CP_AXIS)
        return acc_L, acc_outer, loss_acc, aux_acc

    P = jax.sharding.PartitionSpec
    data_spec = P(None, None, CP_AXIS)
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(None, PP_AXIS), layers_chunked),
            jax.tree.map(lambda _: P(), outer),
            data_spec, data_spec, data_spec,
            _aux_specs(aux_mb),
            P(CP_AXIS),
            P(), P(),
        ),
        out_specs=(
            jax.tree.map(lambda _: P(None, PP_AXIS), layers_chunked),
            jax.tree.map(lambda _: P(), outer),
            P(), P(),
        ),
        axis_names={PP_AXIS, CP_AXIS},
        check_vma=False,
    )
    grads_Lc, grads_outer, loss_ce, aux_tot = fn(
        layers_chunked, outer, tokens, labels, loss_mask, aux_mb,
        st["token_idx_arr"], embed_keys, layer_keys,
    )
    # the out-spec gather concatenates stage shards into axis 1: leaves come
    # back [v, pp*Lc, ...] (chunk-major, then stage, then local layer) —
    # exactly the chunked() order, so one reshape restores [L, ...]
    grads_L = jax.tree.map(
        lambda a: a.reshape(L, *a.shape[2:]), grads_Lc
    )
    grads = dict(grads_outer)
    grads["layers"] = grads_L
    loss = loss_ce + aux_tot
    if with_metrics:
        return loss, grads, _1f1b_metrics(st, loss_ce, aux_tot)
    return loss, grads


def pipeline_loss_fn(cfg, mesh, params, batch: Dict[str, jax.Array], *,
                     dropout_key=None, deterministic=True, rope=None,
                     sp_constraint=None, num_micro=None,
                     embed_fn=None, head_loss_fn=None):
    """Full pipelined loss over the global batch (microbatched).

    batch leaves [gbs, s]; gbs = M * mb. Embedding/head run outside the
    pipeline (see module docstring). ``embed_fn``/``head_loss_fn`` follow the
    1F1B hook contract (_1f1b_setup): unscaled per-microbatch contributions,
    normalizers closed over the full batch; defaults implement the GPT
    family.
    """
    M = num_micro or cfg.parallel.num_micro_batches or 1
    gbs = batch["tokens"].shape[0]
    assert gbs % M == 0
    mb = gbs // M

    def split(x):
        return x.reshape(M, mb, *x.shape[1:])

    tokens = split(batch["tokens"])
    labels = split(batch["labels"])
    loss_mask = split(batch["loss_mask"])
    aux_mb = _split_extra_keys(batch, split)
    token_idx = batch.get("token_idx")  # [s], batch-invariant (zigzag cp)

    if rope is None:
        rope = lm.make_rope_cache(cfg)

    use_dropout = dropout_key is not None and not deterministic
    embed_keys, layer_keys = microbatch_keys(
        dropout_key if use_dropout else None, M
    )

    outer = {k: v for k, v in params.items() if k != "layers"}
    default_embed, default_head = _default_gpt_fns(cfg, batch, use_dropout)
    if embed_fn is None:
        embed_fn = default_embed
    if head_loss_fn is None:
        head_loss_fn = default_head

    # [M, mb, s, h] embeddings (vocab-parallel over tp under pjit); dropout
    # keys per microbatch, matching the pp=1 path (model_forward:149-152)
    if embed_keys is not None:
        hidden = jax.vmap(
            lambda t, a, ke: embed_fn(outer, t, a, ke)
        )(tokens, aux_mb, embed_keys)
    else:
        hidden = jax.vmap(lambda t, a: embed_fn(outer, t, a, None))(tokens, aux_mb)

    hidden, moe_aux = pipeline_apply(
        cfg, mesh, params["layers"], hidden, aux_mb, dropout_key,
        deterministic, rope, token_idx=token_idx, mb_keys=layer_keys,
    )

    loss = microbatched_head_loss(
        head_loss_fn, outer, hidden, labels, loss_mask, aux_mb
    )
    metrics = {"lm loss": loss}
    if cfg.model.num_experts is not None:
        from megatron_llm_tpu.models.moe import aux_loss_coeffs

        # aux_acc summed every microbatch; the pp=1 path averages the
        # per-microbatch aux (loss_from_batch + grad-accum mean) — match it
        balance, z = moe_aux[0] / M, moe_aux[1] / M
        c_bal, c_z = aux_loss_coeffs(cfg)
        loss = loss + c_bal * balance + c_z * z
        metrics["moe aux loss"] = balance
        if c_z:
            metrics["router z loss"] = z  # matches loss_from_batch reporting
    return loss, metrics
