"""Ring attention — context parallelism over the ``cp`` mesh axis.

The reference has **no** context parallelism (SURVEY §2.1: long context is
served by FlashAttention-2 + RoPE scaling + sliding window only); this module
is the TPU-native extension that makes sequence length a first-class sharded
dimension, the way the reference makes hidden/vocab dims sharded via TP.

Design (blockwise ring attention, Liu et al. 2023 style, TPU-native):

* the sequence axis of Q/K/V is sharded over ``cp``; each device holds a
  contiguous (or zigzag-permuted) chunk.
* K/V chunks rotate around the cp ring with ``lax.ppermute`` (one ICI hop per
  step — the collective rides the torus neighbour links), while each device
  accumulates its local Q against every K/V chunk with the online-softmax
  recurrence (running max ``m``, normalizer ``l``, unnormalized output ``o``)
  — the same accumulation the Pallas flash kernel uses per block, lifted to
  the inter-chip level.
* causal masking is computed from explicit *token indices* carried (and
  rotated) alongside K/V, so arbitrary sequence permutations work. That is
  what makes **zigzag load balancing** a pure data transform: device ``i``
  holds chunks ``i`` and ``2*cp-1-i`` of the sequence, so every device sees
  the same amount of unmasked causal work (a contiguous split leaves device 0
  nearly idle and device cp-1 doing all of it).
* the whole loop is a differentiable ``lax.scan``; the backward pass is
  autodiff through the scan, with ``ppermute``'s transpose providing the
  reverse rotation — no hand-written bwd collectives.

GQA is computed grouped (no K/V head expansion), matching ops/attention.py.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from megatron_llm_tpu.core import parallel_state as ps
from megatron_llm_tpu.parallel import compat
from megatron_llm_tpu.parallel.compat import shard_map
from megatron_llm_tpu.ops.attention import NEG_INF

# Row-blocking of the ring online softmax (see _ring_attention_local):
# local seqs above the threshold process Q rows in blocks of this size.
_Q_BLOCK_THRESHOLD = 4096
_Q_BLOCK_ROWS = 2048
_Q_BLOCK_MIN = 256        # floor: below this the scan is latency-bound
_Q_BLOCK_OVER = 4 * _Q_BLOCK_ROWS  # ceiling for the fall-UP path


def _choose_q_block(sq: int) -> int:
    """Pick the Q-row block size for the ring online-softmax scan.

    Blocks must divide sq exactly (the scan reshapes [sq] -> [nb, blk]).
    The largest divisor in [_Q_BLOCK_MIN, _Q_BLOCK_ROWS] wins; for
    non-smooth sq (e.g. prime, or 2*p) whose only small divisors are tiny,
    falling DOWN toward blk=1 would turn one ring step into up to sq
    sequential checkpointed iterations — a severe compile/runtime cliff —
    so we instead fall UP to the smallest divisor above the budget (score
    temps grow proportionally but stay bounded by _Q_BLOCK_OVER). If even
    that would exceed 4x the budget, the config is pathological and we
    refuse with guidance rather than silently compile something terrible.
    """
    if sq <= _Q_BLOCK_THRESHOLD:
        return sq
    divs = [d for d in range(_Q_BLOCK_MIN, _Q_BLOCK_ROWS + 1) if sq % d == 0]
    if divs:
        return max(divs)
    over = min(
        (d for d in range(_Q_BLOCK_ROWS + 1, _Q_BLOCK_OVER + 1)
         if sq % d == 0),
        default=None,
    )
    if over is not None:
        return over
    raise ValueError(
        f"ring attention: local seq length {sq} has no divisor in "
        f"[{_Q_BLOCK_MIN}, {_Q_BLOCK_OVER}] to use as a Q-row block; "
        f"choose seq_len / (2*cp) with a power-of-two (or otherwise "
        f"smooth) factor so the online softmax can be row-blocked."
    )


# ---------------------------------------------------------------------------
# Zigzag load balancing (pure data transform)
# ---------------------------------------------------------------------------


def zigzag_permutation(seq_len: int, cp: int) -> np.ndarray:
    """Permutation p so that tokens p[chunk_i] land on cp-rank i balanced.

    Splits the sequence into 2*cp chunks; rank i holds chunks (i, 2*cp-1-i).
    Under causal masking every rank then attends to the same number of
    unmasked (q, k) pairs.
    """
    assert seq_len % (2 * cp) == 0, (
        f"seq_len {seq_len} must be divisible by 2*cp = {2 * cp} for zigzag"
    )
    c = seq_len // (2 * cp)
    chunks = np.arange(seq_len).reshape(2 * cp, c)
    order = []
    for i in range(cp):
        order.append(chunks[i])
        order.append(chunks[2 * cp - 1 - i])
    return np.concatenate(order)


def apply_zigzag(batch: Dict[str, np.ndarray], cp: int) -> Dict[str, np.ndarray]:
    """Permute every per-token tensor of a batch for zigzag CP sharding.

    Adds ``token_idx`` (the original sequence index of each permuted slot) so
    ring attention can reconstruct the causal structure. Per-token CE loss is
    permutation-invariant under the matching label/mask permutation, so the
    training loss is unchanged.
    """
    seq_keys = ("tokens", "labels", "loss_mask", "position_ids", "segment_ids")
    some = next(v for k, v in batch.items() if k in seq_keys)
    perm = zigzag_permutation(some.shape[1], cp)
    out = dict(batch)
    for k in seq_keys:
        if k in batch and batch[k] is not None:
            out[k] = np.ascontiguousarray(np.asarray(batch[k])[:, perm])
    if "position_ids" not in out or out.get("position_ids") is None:
        # RoPE must still see original positions after the permutation.
        out["position_ids"] = np.broadcast_to(
            perm[None, :], some.shape[:2]
        ).astype(np.int32)
    out["token_idx"] = perm.astype(np.int32)
    return out


# ---------------------------------------------------------------------------
# Flash-in-ring: the Pallas kernel computes each (Q-chunk, KV-chunk) pair
# ---------------------------------------------------------------------------
#
# The jnp ring loop below materializes [.., blk, skv] fp32 score tensors in
# HBM between the two matmuls of every ring step — XLA cannot fuse a matmul
# -> softmax -> matmul chain the way a flash kernel tiles it through VMEM.
# For the CONTIGUOUS chunk layout (token_idx=None; zigzag is opt-in), each
# ring step's masking structure collapses to one of exactly three cases per
# (Q-chunk i, KV-chunk src) pair (equal chunk sizes):
#     src > i   entirely above the causal diagonal  -> skip (lse = -inf)
#     src == i  the diagonal chunk                  -> flash with causal=True
#     src < i   entirely below                      -> flash with causal=False
# so the unmodified kernel covers every case, chunk results merge by their
# log-sum-exp, and the BACKWARD is exact per chunk: FlashAttention's bwd
# only needs the GLOBAL per-row lse and delta = rowsum(do*o) — both of
# which the forward merge produces — so each KV chunk's (dq+, dk, dv)
# contribution is one _bwd kernel call with the global residuals, with dk/dv
# accumulators riding the same ppermute ring home to their owner chip.
# Sliding windows span chunk boundaries at offsets the kernel cannot
# express and fall back to the jnp path. The zigzag layout IS kernelized —
# the striped variant further below (declared via the ``zigzag`` contract
# flag); non-causal permuted batches need no striping at all (their
# masking is order-independent) and use this contiguous ring directly.
# See _dispatch_local for the routing table.


def _flash_ring_blocks(s: int, d: int) -> tuple:
    # the kernel module's single block policy: VMEM cap by head_dim AND the
    # MLT_FLASH_BLOCK_Q/KV sweep overrides (a retune sweep must reach the
    # ring path too, not just plain flash_attention)
    from megatron_llm_tpu.ops.pallas.flash_attention import pick_blocks

    return pick_blocks(s, s, d)


def _ring_perm(cp: int) -> list:
    """The KV-rotation permutation — shared by fwd and bwd so the two ring
    directions can never diverge silently."""
    return [(j, (j + 1) % cp) for j in range(cp)]


def _ring_case_index(src, i, causal):
    """skip(0) / causal-diagonal(1) / unmasked(2) classification of a
    (Q-chunk i, KV-chunk src) pair — THE masking policy of the flash ring,
    shared by forward and backward (a divergence would be a silent
    wrong-gradient bug, not a crash)."""
    if not causal:
        return jnp.int32(2)
    return jnp.where(src == i, jnp.int32(1),
                     jnp.where(src < i, jnp.int32(2), jnp.int32(0)))


def _flash_shapes_ok(s: int, d: int) -> bool:
    return d in (64, 128, 256) and s >= 128 and s % 128 == 0


def _merge_chunk(acc, m_run, l_run, out_t, lse_t):
    """Log-sum-exp merge of one chunk's (normalized out, lse) into the
    running (acc fp32, max, normalizer) — shared by the contiguous and
    striped rings. Guards the all-masked-so-far rows (lse at NEG_INF;
    exp of NEG-NEG would be 1 and poison the merge)."""
    m_new = jnp.maximum(m_run, lse_t)
    alpha = jnp.where(m_run <= NEG_INF * 0.5, 0.0, jnp.exp(m_run - m_new))
    beta = jnp.where(lse_t <= NEG_INF * 0.5, 0.0, jnp.exp(lse_t - m_new))
    acc = acc * alpha[..., None] + out_t * beta[..., None]
    return acc, m_new, l_run * alpha + beta


def _flash_ring_fwd_impl(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv,
                         interpret, axis_name):
    """Returns (out [b,n,s,d] in qh.dtype, global lse [b,n,s,1] fp32).

    ``i`` is this device's cp coordinate, computed by the CALLER outside
    any nested shard_map: lax.axis_index lowers to its own
    manual-computation op, and emitting it where cp is not part of the
    innermost manual set double-binds the axis (sdy verifier error).
    ppermute does not have that problem — it stays inside."""
    from megatron_llm_tpu.ops.pallas.flash_attention import _fwd

    cp = jax.lax.axis_size(axis_name)
    b, n, s, d = qh.shape
    perm = _ring_perm(cp)

    def chunk_cases(kh_t, vh_t, skv3_t):
        def skip():
            # fp32 partials: each chunk output is merged across cp steps,
            # and rounding every partial to bf16 first would add up to cp
            # roundings per element (the jnp ring accumulates fp32 too)
            return (jnp.zeros(qh.shape, jnp.float32),
                    jnp.full((b, n, s, 1), NEG_INF, jnp.float32))

        def diag():
            return tuple(_fwd(qh, kh_t, vh_t, sq3, skv3_t, scale, True,
                              None, bq, bkv, interpret,
                              out_dtype=jnp.float32))

        def full():
            return tuple(_fwd(qh, kh_t, vh_t, sq3, skv3_t, scale, False,
                              None, bq, bkv, interpret,
                              out_dtype=jnp.float32))

        return skip, diag, full

    def step(carry, _):
        acc, m_run, l_run, kh_t, vh_t, skv3_t, src = carry
        out_t, lse_t = lax.switch(_ring_case_index(src, i, causal),
                                  chunk_cases(kh_t, vh_t, skv3_t))
        acc, m_run, l_run = _merge_chunk(acc, m_run, l_run, out_t,
                                         lse_t[..., 0])
        kh_t = lax.ppermute(kh_t, axis_name, perm)
        vh_t = lax.ppermute(vh_t, axis_name, perm)
        if skv3_t is not None:
            skv3_t = lax.ppermute(skv3_t, axis_name, perm)
        return (acc, m_run, l_run, kh_t, vh_t, skv3_t,
                (src - 1) % cp), None

    acc0 = jnp.zeros((b, n, s, d), jnp.float32)
    m0 = jnp.full((b, n, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, s), jnp.float32)
    (acc, m_run, l_run, *_), _ = lax.scan(
        step, (acc0, m0, l0, kh, vh, skv3, i), None, length=cp)
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    out = (acc / l_safe[..., None]).astype(qh.dtype)
    lse = (m_run + jnp.log(l_safe))[..., None]
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_ring(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv, interpret,
                axis_name):
    out, _ = _flash_ring_fwd_impl(qh, kh, vh, sq3, skv3, i, scale, causal,
                                  bq, bkv, interpret, axis_name)
    return out


def _flash_ring_fwd(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv,
                    interpret, axis_name):
    out, lse = _flash_ring_fwd_impl(qh, kh, vh, sq3, skv3, i, scale, causal,
                                    bq, bkv, interpret, axis_name)
    return out, (qh, kh, vh, sq3, skv3, i, out, lse)


def _flash_ring_bwd(scale, causal, bq, bkv, interpret, axis_name,
                    residuals, do):
    from megatron_llm_tpu.ops.pallas.flash_attention import _bwd

    qh, kh, vh, sq3, skv3, i, out, lse = residuals
    cp = jax.lax.axis_size(axis_name)
    perm = _ring_perm(cp)
    # delta = rowsum(do * o) is loop-invariant — computed ONCE here (XLA
    # cannot CSE across scan iterations; recomputing it per ring step would
    # waste cp-1 full-tensor passes), fp32 kernel outputs for the same
    # one-rounding accumulation policy as the forward
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def chunk_cases(kh_t, vh_t, skv3_t):
        def run(causal_flag):
            dq, dk, dv, _, _ = _bwd(
                scale, causal_flag, None, bq, bkv, interpret,
                (qh, kh_t, vh_t, out, lse, sq3, skv3_t), (do,),
                delta=delta, out_dtype=jnp.float32)
            return dq, dk, dv

        def skip():
            return (jnp.zeros(qh.shape, jnp.float32),
                    jnp.zeros(kh.shape, jnp.float32),
                    jnp.zeros(vh.shape, jnp.float32))

        return skip, lambda: run(True), lambda: run(False)

    def step(carry, _):
        dq_acc, dk_acc, dv_acc, kh_t, vh_t, skv3_t, src = carry
        dq_t, dk_t, dv_t = lax.switch(_ring_case_index(src, i, causal),
                                      chunk_cases(kh_t, vh_t, skv3_t))
        dq_acc = dq_acc + dq_t
        # dk/dv accumulators ride the ring WITH their chunk: after cp
        # permutes each chunk's accumulated gradient is back at its owner
        dk_acc = dk_acc + dk_t
        dv_acc = dv_acc + dv_t
        kh_t = lax.ppermute(kh_t, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        vh_t = lax.ppermute(vh_t, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        if skv3_t is not None:
            skv3_t = lax.ppermute(skv3_t, axis_name, perm)
        return (dq_acc, dk_acc, dv_acc, kh_t, vh_t, skv3_t,
                (src - 1) % cp), None

    (dq, dk, dv, *_), _ = lax.scan(
        step,
        (jnp.zeros(qh.shape, jnp.float32), jnp.zeros(kh.shape, jnp.float32),
         jnp.zeros(vh.shape, jnp.float32), kh, vh, skv3, i),
        None, length=cp)
    return (dq.astype(qh.dtype), dk.astype(kh.dtype), dv.astype(vh.dtype),
            None, None, None)


_flash_ring.defvjp(_flash_ring_fwd, _flash_ring_bwd)


# ---------------------------------------------------------------------------
# Striped flash ring: the zigzag layout, kernelized (round 5)
# ---------------------------------------------------------------------------
#
# Under the standard zigzag layout (apply_zigzag: device j holds global
# chunks j and 2cp-1-j of 2cp chunks, concatenated [A_j, B_j]) every
# (q-sub, kv-sub) pair is again a contiguous block pair, so the kernel
# covers it at half-chunk granularity. With causal masking only THREE of
# the four pairs are ever live:
#     A_i vs A_src   the contiguous 3-way case on (src, i)
#     B_i vs A_src   q chunk 2cp-1-i >= cp > src      -> always unmasked
#     B_i vs B_src   compares (2cp-1-i, 2cp-1-src)    -> the 3-way case
#                    with the roles of src and i SWAPPED
#     A_i vs B_src   kv chunk 2cp-1-src >= cp > i     -> always masked
# which is what makes zigzag balanced: each device does ~1.5 half-chunk
# kernels per step regardless of its rank, vs the contiguous layout where
# step t idles every device below rank t. (Callers declare the layout via
# the ``zigzag`` contract flag — token order is runtime data; non-causal
# permuted batches need no striping at all since their masking is
# order-independent and the plain flash ring is used.)


def _zz_cases(i, src, causal):
    case_aa = _ring_case_index(src, i, causal)
    case_bb = _ring_case_index(i, src, causal)
    return case_aa, case_bb


def _split_half(x, axis):
    c = x.shape[axis] // 2
    return (lax.slice_in_dim(x, 0, c, axis=axis),
            lax.slice_in_dim(x, c, 2 * c, axis=axis))


def _flash_ring_zz_fwd_impl(qh, kh, vh, sq3, skv3, i, scale, causal, bq,
                            bkv, interpret, axis_name):
    from megatron_llm_tpu.ops.pallas.flash_attention import _fwd

    assert causal, "striped ring is causal-only (see module note)"
    cp = jax.lax.axis_size(axis_name)
    b, n, s, d = qh.shape
    c = s // 2
    perm = _ring_perm(cp)
    qA, qB = _split_half(qh, 2)
    sqA, sqB = _split_half(sq3, 2) if sq3 is not None else (None, None)

    def fwd_pair(q_, k_, v_, sq_, skv_, causal_flag):
        return tuple(_fwd(q_, k_, v_, sq_, skv_, scale, causal_flag, None,
                          bq, bkv, interpret, out_dtype=jnp.float32))

    def skip_out():
        return (jnp.zeros((b, n, c, d), jnp.float32),
                jnp.full((b, n, c, 1), NEG_INF, jnp.float32))

    def step(carry, _):
        accA, mA, lA, accB, mB, lB, kh_t, vh_t, skv3_t, src = carry
        kA, kB = _split_half(kh_t, 2)
        vA, vB = _split_half(vh_t, 2)
        skvA, skvB = (_split_half(skv3_t, 2) if skv3_t is not None
                      else (None, None))
        case_aa, case_bb = _zz_cases(i, src, causal)
        outAA, lseAA = lax.switch(case_aa, (
            skip_out,
            lambda: fwd_pair(qA, kA, vA, sqA, skvA, True),
            lambda: fwd_pair(qA, kA, vA, sqA, skvA, False)))
        accA, mA, lA = _merge_chunk(accA, mA, lA, outAA, lseAA[..., 0])
        outBA, lseBA = fwd_pair(qB, kA, vA, sqB, skvA, False)
        accB, mB, lB = _merge_chunk(accB, mB, lB, outBA, lseBA[..., 0])
        outBB, lseBB = lax.switch(case_bb, (
            skip_out,
            lambda: fwd_pair(qB, kB, vB, sqB, skvB, True),
            lambda: fwd_pair(qB, kB, vB, sqB, skvB, False)))
        accB, mB, lB = _merge_chunk(accB, mB, lB, outBB, lseBB[..., 0])
        kh_t = lax.ppermute(kh_t, axis_name, perm)
        vh_t = lax.ppermute(vh_t, axis_name, perm)
        if skv3_t is not None:
            skv3_t = lax.ppermute(skv3_t, axis_name, perm)
        return (accA, mA, lA, accB, mB, lB, kh_t, vh_t, skv3_t,
                (src - 1) % cp), None

    z = lambda: jnp.zeros((b, n, c, d), jnp.float32)  # noqa: E731
    mneg = lambda: jnp.full((b, n, c), NEG_INF, jnp.float32)  # noqa: E731
    l0 = lambda: jnp.zeros((b, n, c), jnp.float32)  # noqa: E731
    (accA, mA, lA, accB, mB, lB, *_), _ = lax.scan(
        step, (z(), mneg(), l0(), z(), mneg(), l0(), kh, vh, skv3, i),
        None, length=cp)

    def fin(acc, m_run, l_run):
        l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
        return (acc / l_safe[..., None]).astype(qh.dtype), \
            (m_run + jnp.log(l_safe))[..., None]

    outA, lseA = fin(accA, mA, lA)
    outB, lseB = fin(accB, mB, lB)
    return (jnp.concatenate([outA, outB], axis=2),
            jnp.concatenate([lseA, lseB], axis=2))


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_ring_zz(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv,
                   interpret, axis_name):
    out, _ = _flash_ring_zz_fwd_impl(qh, kh, vh, sq3, skv3, i, scale,
                                     causal, bq, bkv, interpret, axis_name)
    return out


def _flash_ring_zz_fwd(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv,
                       interpret, axis_name):
    out, lse = _flash_ring_zz_fwd_impl(qh, kh, vh, sq3, skv3, i, scale,
                                       causal, bq, bkv, interpret,
                                       axis_name)
    return out, (qh, kh, vh, sq3, skv3, i, out, lse)


def _flash_ring_zz_bwd(scale, causal, bq, bkv, interpret, axis_name,
                       residuals, do):
    from megatron_llm_tpu.ops.pallas.flash_attention import _bwd

    qh, kh, vh, sq3, skv3, i, out, lse = residuals
    cp = jax.lax.axis_size(axis_name)
    b, n, s, d = qh.shape
    nkv = kh.shape[1]
    c = s // 2
    perm = _ring_perm(cp)
    qA, qB = _split_half(qh, 2)
    sqA, sqB = _split_half(sq3, 2) if sq3 is not None else (None, None)
    outA, outB = _split_half(out, 2)
    lseA, lseB = _split_half(lse, 2)
    doA, doB = _split_half(do, 2)
    # loop-invariant delta, computed once per q-sub (same rationale as the
    # contiguous bwd)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    deltaA, deltaB = _split_half(delta, 2)

    def run_pair(q_, k_, v_, o_, lse_, do_, delta_, sq_, skv_, causal_flag):
        dq, dk, dv, _, _ = _bwd(
            scale, causal_flag, None, bq, bkv, interpret,
            (q_, k_, v_, o_, lse_, sq_, skv_), (do_,),
            delta=delta_, out_dtype=jnp.float32)
        return dq, dk, dv

    def zeros3():
        return (jnp.zeros((b, n, c, d), jnp.float32),
                jnp.zeros((b, nkv, c, d), jnp.float32),
                jnp.zeros((b, nkv, c, d), jnp.float32))

    def step(carry, _):
        dqA, dqB, dk_acc, dv_acc, kh_t, vh_t, skv3_t, src = carry
        kA, kB = _split_half(kh_t, 2)
        vA, vB = _split_half(vh_t, 2)
        skvA, skvB = (_split_half(skv3_t, 2) if skv3_t is not None
                      else (None, None))
        case_aa, case_bb = _zz_cases(i, src, causal)
        dqAA, dkAA, dvAA = lax.switch(case_aa, (
            zeros3,
            lambda: run_pair(qA, kA, vA, outA, lseA, doA, deltaA,
                             sqA, skvA, True),
            lambda: run_pair(qA, kA, vA, outA, lseA, doA, deltaA,
                             sqA, skvA, False)))
        dqBA, dkBA, dvBA = run_pair(qB, kA, vA, outB, lseB, doB, deltaB,
                                    sqB, skvA, False)
        dqBB, dkBB, dvBB = lax.switch(case_bb, (
            zeros3,
            lambda: run_pair(qB, kB, vB, outB, lseB, doB, deltaB,
                             sqB, skvB, True),
            lambda: run_pair(qB, kB, vB, outB, lseB, doB, deltaB,
                             sqB, skvB, False)))
        dqA = dqA + dqAA
        dqB = dqB + dqBA + dqBB
        dk_acc = dk_acc + jnp.concatenate([dkAA + dkBA, dkBB], axis=2)
        dv_acc = dv_acc + jnp.concatenate([dvAA + dvBA, dvBB], axis=2)
        kh_t = lax.ppermute(kh_t, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        vh_t = lax.ppermute(vh_t, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        if skv3_t is not None:
            skv3_t = lax.ppermute(skv3_t, axis_name, perm)
        return (dqA, dqB, dk_acc, dv_acc, kh_t, vh_t, skv3_t,
                (src - 1) % cp), None

    (dqA, dqB, dk, dv, *_), _ = lax.scan(
        step,
        (jnp.zeros((b, n, c, d), jnp.float32),
         jnp.zeros((b, n, c, d), jnp.float32),
         jnp.zeros(kh.shape, jnp.float32), jnp.zeros(vh.shape, jnp.float32),
         kh, vh, skv3, i),
        None, length=cp)
    dq = jnp.concatenate([dqA, dqB], axis=2)
    return (dq.astype(qh.dtype), dk.astype(kh.dtype), dv.astype(vh.dtype),
            None, None, None)


_flash_ring_zz.defvjp(_flash_ring_zz_fwd, _flash_ring_zz_bwd)


def _ring_attention_flash_core(q, k, v, seg_q, seg_kv, i, *, axis_name,
                               scale, causal, interpret, striped=False):
    """[b, s, n, d] wrapper over the kernel-layout ring (see module note).
    Every mesh axis must already be manual in the calling context; ``i``
    is the cp coordinate computed where cp was bound (see
    _flash_ring_fwd_impl's docstring); ``striped`` selects the zigzag
    half-chunk variant."""
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    sq3 = seg_q.astype(jnp.int32)[:, None, :] if seg_q is not None else None
    skv3 = (seg_kv.astype(jnp.int32)[:, None, :]
            if seg_kv is not None else None)
    sub = 2 if striped else 1
    bq, bkv = _flash_ring_blocks(q.shape[1] // sub, q.shape[-1])
    ring = _flash_ring_zz if striped else _flash_ring
    out = ring(qh, kh, vh, sq3, skv3, i, scale, causal, bq, bkv,
               interpret, axis_name)
    return out.transpose(0, 2, 1, 3)


def _ring_attention_flash(q, k, v, seg_q, seg_kv, *, axis_name, scale,
                          causal, interpret, striped=False):
    """Dispatch the flash ring, manualizing any remaining auto mesh axes.

    From pjit-land the enclosing ring shard_map is full-manual and the
    kernels run directly; from the pipeline body only {pp, cp} are manual,
    and Mosaic kernels reject being left under ANY auto axis — so the
    whole ring loop (kernels + ppermutes; cp stays bound from the outer
    context) nests one shard_map over the rest, batch on (dp, ep), heads
    on tp (same composition as ops/attention._flash_sharded)."""
    abstract = compat.get_abstract_mesh()
    auto = set()
    if abstract is not None and not abstract.empty and abstract.manual_axes:
        auto = set(abstract.axis_names) - set(abstract.manual_axes)
    kw = dict(axis_name=axis_name, scale=scale, causal=causal,
              interpret=interpret, striped=striped)
    # the cp coordinate is computed HERE — where the caller's context binds
    # cp — and passed in: lax.axis_index emitted inside the nested
    # shard_map would double-bind the axis (sdy verifier error)
    i = jax.lax.axis_index(axis_name)
    if not auto:
        return _ring_attention_flash_core(q, k, v, seg_q, seg_kv, i, **kw)
    qs = P(ps.DATA_AXES, None, ps.TP_AXIS, None)
    segs = P(ps.DATA_AXES, None)
    if seg_q is None:
        fn = shard_map(
            lambda q_, k_, v_, i_: _ring_attention_flash_core(
                q_, k_, v_, None, None, i_, **kw),
            mesh=abstract, in_specs=(qs, qs, qs, P()), out_specs=qs,
            axis_names=auto, check_vma=False)
        return fn(q, k, v, i)
    fn = shard_map(
        lambda q_, k_, v_, sq_, skv_, i_: _ring_attention_flash_core(
            q_, k_, v_, sq_, skv_, i_, **kw),
        mesh=abstract, in_specs=(qs, qs, qs, segs, segs, P()), out_specs=qs,
        axis_names=auto, check_vma=False)
    return fn(q, k, v, seg_q, seg_kv, i)


# ---------------------------------------------------------------------------
# The ring loop (runs inside shard_map; cp axis is manual)
# ---------------------------------------------------------------------------


def _ring_attention_local(
    q: jax.Array,  # [b, sq_loc, n, d]
    k: jax.Array,  # [b, skv_loc, nkv, d]
    v: jax.Array,  # [b, skv_loc, nkv, d]
    q_idx: jax.Array,    # [sq_loc] global token indices of local Q rows
    kv_idx: jax.Array,   # [skv_loc] global token indices of local K/V rows
    seg_q: Optional[jax.Array],   # [b, sq_loc] or None
    seg_kv: Optional[jax.Array],  # [b, skv_loc] or None
    *,
    axis_name: str,
    scale: float,
    causal: bool,
    sliding_window: Optional[int],
) -> jax.Array:
    cp = jax.lax.axis_size(axis_name)
    b, sq, n, d = q.shape
    nkv = k.shape[2]
    g = n // nkv
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, nkv, g, d)

    # Row-block the online softmax: a full [.., sq, skv] fp32 score tensor
    # is ~8.6 GiB per layer at the 32K/cp=2 BASELINE config (heads 8, 16K x
    # 16K) and OOMs v5p during backward (tools/aot_scale_check.py found
    # this). Q rows are independent in online softmax, so scanning blocks
    # of rows inside each ring step bounds the live score temps to
    # [.., blk, skv] with bitwise-identical results.
    blk = _choose_q_block(sq)
    nb = sq // blk

    # send chunk i -> i+1 each step; after t steps a device holds the K/V
    # chunk of cp-rank (i - t) % cp. The rotated kv_idx tracks that for us.
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def allowed_mask(qi_b, kv_idx_t, seg_q_b, seg_kv_t):
        # [1 or b, blk, skv] for one row block
        ok = jnp.ones((1, qi_b.shape[0], k.shape[1]), dtype=bool)
        qi = qi_b[:, None]
        ki = kv_idx_t[None, :]
        if causal:
            ok &= (qi >= ki)[None]
        if sliding_window is not None:
            ok &= (qi - ki < sliding_window)[None]
        if seg_q is not None:
            ok = ok & (seg_q_b[:, :, None] == seg_kv_t[:, None, :])
        return ok

    def step(carry, _):
        o, m, l, k_t, v_t, kv_idx_t, seg_kv_t = carry
        kf = k_t.astype(jnp.float32)
        vf = v_t.astype(jnp.float32)

        def row_block(_, xs):
            qg_b, qi_b, seg_q_b, o_b, m_b, l_b = xs
            # scores [b, nkv, g, blk, skv] in fp32
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg_b, kf)
            ok = allowed_mask(qi_b, kv_idx_t, seg_q_b, seg_kv_t)[:, None, None]
            s_masked = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m_b, s_masked.max(axis=-1))
            # mask applied to p directly — never rely on exp(-inf - -inf)
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m_b - m_new)
            l_new = l_b * alpha + p.sum(axis=-1)
            o_new = o_b * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vf
            )
            return None, (o_new, m_new, l_new)

        def rows(x, axis):  # [.., sq, ..] -> [nb, .., blk, ..] for scan xs
            return jnp.moveaxis(
                x.reshape(*x.shape[:axis], nb, blk, *x.shape[axis + 1:]),
                axis, 0)

        qg_r = rows(qg, 1)                       # [nb, b, blk, nkv, g, d]
        qi_r = q_idx.reshape(nb, blk)
        seg_q_r = (rows(seg_q, 1) if seg_q is not None
                   else jnp.zeros((nb, 1, blk), jnp.int32))
        o_r = rows(o, 3)                         # [nb, b, nkv, g, blk, d]
        m_r = rows(m, 3)
        l_r = rows(l, 3)
        # checkpoint per block: without it, autodiff-of-scan STACKS every
        # block's [.., blk, skv] probability tensor as residuals — 16 GiB
        # at the 32K config, defeating the blocking. Recomputing scores in
        # the backward is the same FLOPs-for-memory trade flash attention
        # makes.
        _, (o2, m2, l2) = lax.scan(
            jax.checkpoint(row_block), None,
            (qg_r, qi_r, seg_q_r, o_r, m_r, l_r))

        def back(x, axis, tail):  # [nb, .., blk, ..] -> [.., sq, ..]
            y = jnp.moveaxis(x, 0, axis)
            return y.reshape(*y.shape[:axis], sq, *y.shape[axis + 2:]) \
                if tail else y.reshape(*y.shape[:axis], sq)

        o = back(o2, 3, True)
        m = back(m2, 3, False)
        l = back(l2, 3, False)
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        kv_idx_t = lax.ppermute(kv_idx_t, axis_name, perm)
        if seg_kv_t is not None:
            seg_kv_t = lax.ppermute(seg_kv_t, axis_name, perm)
        return (o, m, l, k_t, v_t, kv_idx_t, seg_kv_t), None

    o0 = jnp.zeros((b, nkv, g, sq, d), jnp.float32)
    m0 = jnp.full((b, nkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, sq), jnp.float32)
    (o, _, l, *_), _ = lax.scan(
        step, (o0, m0, l0, k, v, kv_idx, seg_kv), None, length=cp
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (o / l_safe[..., None]).transpose(0, 3, 1, 2, 4)  # [b, sq, nkv, g, d]
    return out.reshape(b, sq, n, d).astype(q.dtype)


def _local_indices(token_idx: Optional[jax.Array], s_local: int, axis_name: str):
    """Global token indices of this device's chunk (contiguous by default)."""
    if token_idx is not None:
        return token_idx
    return jax.lax.axis_index(axis_name) * s_local + jnp.arange(s_local)


# ---------------------------------------------------------------------------
# Public entry: shard_map over the (dp, cp, tp) mesh
# ---------------------------------------------------------------------------


def ring_attention_manual(
    q: jax.Array,  # [b, s_local, n, d] — cp-LOCAL shards
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: Optional[jax.Array] = None,  # [b, s_local]
    token_idx: Optional[jax.Array] = None,    # [s_local]
    causal: bool = True,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    zigzag: bool = False,
) -> jax.Array:
    """Ring attention for callers already inside a shard_map that manualizes
    ``cp`` (e.g. the pipeline body, parallel/pipeline.py): operates on local
    seq shards directly, no inner shard_map."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _dispatch_local(
        q, k, v, segment_ids, token_idx,
        axis_name=ps.CP_AXIS, scale=scale, causal=causal,
        sliding_window=sliding_window, zigzag=zigzag,
    )


def _dispatch_local(q, k, v, seg, tok, *, axis_name, scale, causal,
                    sliding_window, zigzag=False):
    """Route a cp-local attention call to the fastest correct path:

    * contiguous chunks (no token_idx)         -> flash ring
    * permuted order but NON-causal            -> flash ring (order-
      independent masking: causal off, segments compare by value)
    * causal + declared standard zigzag layout -> striped flash ring
    * anything else (sliding windows, custom permutations, off-tile
      shapes, non-TPU targets)                 -> jnp online-softmax ring

    ``zigzag`` is a CONTRACT flag (cfg --cp_zigzag / apply_zigzag): token
    order is runtime data, so the caller declares the standard layout
    rather than the dispatcher inspecting it.
    """
    from megatron_llm_tpu.core.parallel_state import target_platform
    from megatron_llm_tpu.ops.attention import announce_path

    if target_platform() == "tpu" and sliding_window is None:
        if tok is None and _flash_shapes_ok(q.shape[1], q.shape[-1]):
            announce_path("ring", "pallas", "contiguous chunks")
            return _ring_attention_flash(
                q, k, v, seg, seg, axis_name=axis_name, scale=scale,
                causal=causal, interpret=False)
        if (tok is not None and not causal
                and _flash_shapes_ok(q.shape[1], q.shape[-1])):
            announce_path("ring", "pallas", "permuted, non-causal")
            return _ring_attention_flash(
                q, k, v, seg, seg, axis_name=axis_name, scale=scale,
                causal=False, interpret=False)
        if (tok is not None and causal and zigzag and q.shape[1] % 2 == 0
                and _flash_shapes_ok(q.shape[1] // 2, q.shape[-1])):
            announce_path("ring", "pallas", "striped zigzag")
            return _ring_attention_flash(
                q, k, v, seg, seg, axis_name=axis_name, scale=scale,
                causal=True, interpret=False, striped=True)
    announce_path(
        "ring", "jnp",
        f"target platform is {target_platform()}"
        if target_platform() != "tpu"
        else "sliding window" if sliding_window is not None
        else "undeclared token permutation or off-tile shape")
    idx = _local_indices(tok, q.shape[1], axis_name)
    return _ring_attention_local(
        q, k, v, idx, idx, seg, seg,
        axis_name=axis_name, scale=scale, causal=causal,
        sliding_window=sliding_window,
    )


def cp_is_manual() -> bool:
    """True when tracing inside a shard_map that already binds the cp axis."""
    abstract = compat.get_abstract_mesh()
    return (
        abstract is not None
        and not abstract.empty
        and ps.CP_AXIS in set(abstract.manual_axes)
    )


def ring_attention(
    q: jax.Array,  # [b, s, n, d] — global (pjit-land) arrays
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: Optional[jax.Array] = None,  # [b, s]
    token_idx: Optional[jax.Array] = None,    # [s] original indices (zigzag)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    zigzag: bool = False,
) -> jax.Array:
    """Context-parallel attention: seq over ``cp``, heads over ``tp``,
    batch over ``dp``.

    Called from the ops/attention dispatcher when the active mesh has cp > 1.
    From pjit-land it wraps the ring loop in shard_map; from inside an
    enclosing shard_map that already manualizes cp it runs locally.
    ``zigzag`` declares the standard apply_zigzag layout (see
    _dispatch_local).
    """
    if cp_is_manual():
        return ring_attention_manual(
            q, k, v, segment_ids=segment_ids, token_idx=token_idx,
            causal=causal, sliding_window=sliding_window, scale=scale,
            zigzag=zigzag,
        )
    mesh = mesh or ps.get_global_mesh()
    cp = mesh.shape.get(ps.CP_AXIS, 1)
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    assert q.shape[1] % cp == 0, (
        f"seq_len {q.shape[1]} not divisible by cp {cp}"
    )

    qs = P(ps.DATA_AXES, ps.CP_AXIS, ps.TP_AXIS, None)
    segs = P(ps.DATA_AXES, ps.CP_AXIS)
    idxs = P(ps.CP_AXIS)
    s_local = q.shape[1] // cp

    kw = dict(axis_name=ps.CP_AXIS, scale=scale, causal=causal,
              sliding_window=sliding_window, zigzag=zigzag)

    def local(q_, k_, v_, seg_=None, tok_=None):
        return _dispatch_local(q_, k_, v_, seg_, tok_, **kw)

    in_specs = [qs, qs, qs]
    args = [q, k, v]
    fn = local
    if segment_ids is not None and token_idx is not None:
        fn = lambda q_, k_, v_, s_, t_: local(q_, k_, v_, seg_=s_, tok_=t_)
        in_specs += [segs, idxs]
        args += [segment_ids, token_idx]
    elif segment_ids is not None:
        fn = lambda q_, k_, v_, s_: local(q_, k_, v_, seg_=s_)
        in_specs += [segs]
        args += [segment_ids]
    elif token_idx is not None:
        fn = lambda q_, k_, v_, t_: local(q_, k_, v_, tok_=t_)
        in_specs += [idxs]
        args += [token_idx]

    mapped = shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=qs, check_vma=False
    )
    return mapped(*args)
