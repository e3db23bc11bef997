"""Vocab-parallel cross entropy.

Reference: megatron/core/tensor_parallel/cross_entropy.py:14-175 — computes
softmax-CE over vocab-sharded logits without materializing the full-vocab
softmax on any rank, using three TP all-reduces (max, predicted-logit, sum-exp),
plus optional label smoothing and ``vocab_parallel_max_indices`` for accuracy
metrics.

Two TPU paths:

* :func:`softmax_cross_entropy` — pure jnp, used under ``pjit`` where logits
  carry a vocab-axis sharding; XLA lowers the reductions to the same psum
  pattern automatically. This is the default path.
* :func:`vocab_parallel_cross_entropy` — explicit shard_map formulation over a
  named tp axis, semantics matched line-for-line to the reference for testing
  and for use inside hand-sharded regions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Per-token CE loss; logits [..., vocab] (possibly vocab-sharded), labels [...].

    fp32 internal math regardless of logits dtype (the reference upcasts via
    ``fp16_lm_cross_entropy=False`` default, gpt_model.py:34-40).
    """
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    sum_exp = jnp.sum(jnp.exp(shifted), axis=-1)
    log_z = jnp.log(sum_exp)
    predicted = jnp.take_along_axis(shifted, labels[..., None], axis=-1)[..., 0]
    loss = log_z - predicted
    if label_smoothing > 0.0:
        # reference cross_entropy.py:95-115: J = (1-eps)ce + eps/K * sum(-logprob)
        smoothing = label_smoothing * vocab / (vocab - 1)
        log_probs = shifted - log_z[..., None]
        mean_log = jnp.mean(log_probs, axis=-1)
        loss = (1.0 - smoothing) * loss - smoothing * mean_log
    return loss


def chunked_softmax_cross_entropy_from_hidden(
    hidden: jax.Array,      # [..., h] final-normed hidden states
    head_kernel: jax.Array,  # [h, v] (tied embedding passed transposed)
    labels: jax.Array,       # [...] int
    num_chunks: int,
    head_bias: jax.Array | None = None,  # [v]
) -> jax.Array:
    """Per-token CE fused with the LM-head matmul, scanned over vocab chunks.

    The default path materializes the full [..., v] fp32 logits before
    :func:`softmax_cross_entropy`; at large vocab x long seq x big
    micro-batch that tensor dominates activation memory (vocab 32k, mbs 16,
    seq 1024 -> 2 GiB fp32). Here a ``lax.scan`` over ``num_chunks`` vocab
    slices keeps only [..., v/num_chunks] logits live at a time, carrying
    the running (max, sum-exp, target-logit) triple — the same three
    quantities the reference's vocab-PARALLEL CE tracks across TP ranks
    (cross_entropy.py:21-60), re-cut along the vocab axis sequentially
    instead of spatially. The chunk body is rematerialized so the backward
    also never holds more than one chunk's logits.

    Gradient-exact (not an approximation): d(loss)/d(logits_c) is recomputed
    per chunk from the carried log-partition.
    """
    v = head_kernel.shape[-1]
    assert num_chunks > 0 and v % num_chunks == 0, (v, num_chunks)
    vc = v // num_chunks
    lead = hidden.shape[:-1]

    @jax.checkpoint  # bwd re-runs the chunk GEMM instead of saving logits
    def chunk(carry, off):
        m, s, tgt = carry
        # slice in place: the kernel keeps its native layout/sharding (a
        # pre-reshaped [nc, h, vc] xs would copy + re-lay-out the whole
        # kernel every loss call and fight the tp vocab sharding)
        wc = jax.lax.dynamic_slice_in_dim(head_kernel, off, vc, axis=1)
        logits_c = (hidden @ wc).astype(jnp.float32)
        if head_bias is not None:
            logits_c = logits_c + jax.lax.dynamic_slice_in_dim(
                head_bias, off, vc, axis=0
            )
        m_c = jax.lax.stop_gradient(jnp.max(logits_c, axis=-1))
        m_new = jnp.maximum(m, m_c)
        scale_old = jnp.exp(m - m_new)
        s = s * scale_old + jnp.sum(
            jnp.exp(logits_c - m_new[..., None]), axis=-1
        )
        local = labels - off
        in_chunk = (local >= 0) & (local < vc)
        safe = jnp.clip(local, 0, vc - 1)
        picked = jnp.take_along_axis(logits_c, safe[..., None], -1)[..., 0]
        tgt = jnp.where(in_chunk, picked, tgt)
        return (m_new, s, tgt), None

    init = (
        jnp.full(lead, -jnp.inf, jnp.float32),
        jnp.zeros(lead, jnp.float32),
        jnp.zeros(lead, jnp.float32),
    )
    (m, s, tgt), _ = jax.lax.scan(chunk, init, jnp.arange(num_chunks) * vc)
    return jnp.log(s) + m - tgt


def vocab_parallel_cross_entropy(
    logits_shard: jax.Array,
    labels: jax.Array,
    axis_name: str = "tp",
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Explicit TP formulation for use inside shard_map over ``axis_name``.

    ``logits_shard`` [..., vocab/t] is this rank's contiguous vocab slice
    (rank r owns [r*vp, (r+1)*vp)); ``labels`` are global vocab ids,
    replicated. Three psums mirror cross_entropy.py:21,52,60.
    """
    logits_shard = logits_shard.astype(jnp.float32)
    vp = logits_shard.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    vocab_start = rank * vp

    # stop_gradient BEFORE pmax: the max shift is gradient-free anyway and
    # pmax has no differentiation rule (hit by the pp-vocab head's vjp)
    local_max = jax.lax.stop_gradient(jnp.max(logits_shard, axis=-1))
    global_max = jax.lax.pmax(local_max, axis_name)
    shifted = logits_shard - global_max[..., None]

    exp = jnp.exp(shifted)
    sum_exp = jax.lax.psum(jnp.sum(exp, axis=-1), axis_name)
    log_z = jnp.log(sum_exp)

    # predicted logit: mask labels outside this rank's slice, gather, psum.
    local_labels = labels - vocab_start
    in_range = (local_labels >= 0) & (local_labels < vp)
    safe = jnp.clip(local_labels, 0, vp - 1)
    picked = jnp.take_along_axis(shifted, safe[..., None], axis=-1)[..., 0]
    predicted = jax.lax.psum(jnp.where(in_range, picked, 0.0), axis_name)

    loss = log_z - predicted
    if label_smoothing > 0.0:
        vocab = vp * jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
        smoothing = label_smoothing * vocab / (vocab - 1.0)
        log_probs = shifted - log_z[..., None]
        mean_log = jax.lax.psum(jnp.sum(log_probs, axis=-1), axis_name) / vocab
        loss = (1.0 - smoothing) * loss - smoothing * mean_log
    return loss


def vocab_parallel_max_indices(
    logits_shard: jax.Array, axis_name: str = "tp"
) -> jax.Array:
    """Global argmax over vocab-sharded logits (cross_entropy.py:146-175),
    used by the accuracy metric. Returns global vocab ids."""
    vp = logits_shard.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    local_max = jnp.max(logits_shard, axis=-1)
    local_idx = jnp.argmax(logits_shard, axis=-1) + rank * vp
    # combine (max, idx) across ranks: pick idx of the global max
    all_max = jax.lax.all_gather(local_max, axis_name)  # [t, ...]
    all_idx = jax.lax.all_gather(local_idx, axis_name)
    winner = jnp.argmax(all_max, axis=0)
    return jnp.take_along_axis(all_idx, winner[None], axis=0)[0]
