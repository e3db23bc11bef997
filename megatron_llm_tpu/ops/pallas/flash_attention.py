"""Pallas TPU flash attention (FlashAttention-2 style), fwd + bwd.

Replaces the reference's external FlashAttention-2 CUDA dependency
(transformer.py:9,518-600: flash_attn_func with causal, GQA, sliding-window)
and the fused scaled-masked-softmax CUDA kernels (fused_kernels/, subsumed —
the softmax never materializes).

Design (blockwise online softmax, one pass over KV per Q block):

* layout [b, heads, seq, head_dim]; grid (b*n, num_q_blocks, num_kv_blocks)
  with the KV axis innermost — on TPU the grid is a sequential loop, so VMEM
  scratch (running max m, normalizer l, fp32 accumulator) carries across KV
  iterations for a fixed Q block.
* GQA native: K/V keep n_kv heads; the Q-head grid index maps to kv head
  ``h // group`` in the BlockSpec index map — no broadcast-expand (the
  reference expands K/V at transformer.py:459-466).
* causal + sliding-window + segment-id masking via broadcasted iota on
  *global* positions; fully-masked KV blocks are skipped with @pl.when.
* backward: two kernels (dq; dk/dv fused) recomputing p from the saved
  logsumexp — the standard flash-2 residual scheme (saves q,k,v,o,lse).

Numerics: logits and softmax in fp32 (matches attention_softmax_in_fp32 +
the XLA fallback in ops/attention.py); accumulators fp32; outputs cast to the
input dtype.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

# compile-TARGET platform: AOT lowering for a TPU topology on a CPU
# host must compile the real kernel, not interpret mode
from megatron_llm_tpu.core.parallel_state import target_platform
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _run_block(q_off, kv_off, block_q, block_kv, causal, sliding_window):
    """Whether any (q, kv) pair in this block tile can be unmasked."""
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, q_off + block_q - 1 >= kv_off)
    if sliding_window is not None:
        run = jnp.logical_and(run, kv_off + block_kv - 1 > q_off - sliding_window)
    return run


def _mask(
    q_off, kv_off, block_q, block_kv, causal, sliding_window,
    seg_q, seg_kv,
):
    """Additive fp32 mask [block_q, block_kv] from global offsets."""
    q_ids = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kv_ids = kv_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    allowed = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        allowed &= q_ids >= kv_ids
    if sliding_window is not None:
        allowed &= (q_ids - kv_ids) < sliding_window
    if seg_q is not None:
        allowed &= seg_q.reshape(block_q, 1) == seg_kv.reshape(1, block_kv)
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    # refs (segment refs present only when segmented)
    *refs,
    scale: float,
    causal: bool,
    sliding_window: Optional[int],
    block_q: int,
    block_kv: int,
    kv_seq_len: int,
    segmented: bool,
):
    if segmented:
        q_ref, k_ref, v_ref, segq_ref, segkv_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        segq_ref = segkv_ref = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = qi * block_q
    kv_off = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # skip blocks entirely above the diagonal / outside the window
    run = _run_block(q_off, kv_off, block_q, block_kv, causal, sliding_window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]
        seg_q = segq_ref[0, 0] if segmented else None
        seg_kv = segkv_ref[0, 0] if segmented else None
        if causal or sliding_window is not None or segmented:
            s = s + _mask(q_off, kv_off, block_q, block_kv, causal,
                          sliding_window, seg_q, seg_kv)

        m_prev = m_s[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        # guard rows that are fully masked SO FAR (m_cur still -inf — happens
        # under sliding window when early KV blocks are entirely out-of-window):
        # exp(-inf - -inf) would be 1, poisoning the accumulator.
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur[:, None]))
        l_cur = alpha * l_s[:, 0] + jnp.sum(p, axis=1)
        m_s[:, 0] = m_cur
        l_s[:, 0] = l_cur
        v = v_ref[0, 0].astype(jnp.float32)
        acc_s[:] = acc_s[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_s[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_s[:] / l_safe[:, None]).astype(o_ref.dtype)
        # trailing singleton keeps the (sublane, lane) tile legal on TPU
        lse_ref[0, 0, :, 0] = (m_s[:, 0] + jnp.log(l_safe)).astype(jnp.float32)


def _fwd(
    q, k, v, seg_q, seg_kv, scale, causal, sliding_window, block_q, block_kv,
    interpret, out_dtype=None,
):
    """``out_dtype``: ring callers (parallel/ring.py) accumulate per-chunk
    partials across cp steps and request fp32 to avoid one extra rounding
    per chunk; the default (q.dtype) is the plain-attention contract."""
    b, n, sq, d = q.shape
    _, nkv, skv, _ = k.shape
    g = n // nkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (
        f"seq lengths ({sq},{skv}) must divide blocks ({block_q},{block_kv})"
    )
    grid = (b * n, sq // block_q, skv // block_kv)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bh, qi, ki: (bh // n, (bh % n) // g, ki, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bh, qi, ki: (bh // n, (bh % n) // g, ki, 0)),
    ]
    args = [q, k, v]
    segmented = seg_q is not None
    if segmented:
        # [b, 1, s] layout: the unit middle dim keeps the block's
        # second-to-last dimension equal to the array's (TPU tiling rule)
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh // n, 0, qi)),
            pl.BlockSpec((1, 1, block_kv), lambda bh, qi, ki: (bh // n, 0, ki)),
        ]
        args += [seg_q, seg_kv]

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sliding_window=sliding_window,
        block_q=block_q, block_kv=block_kv, kv_seq_len=skv, segmented=segmented,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, n, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    *refs, scale, causal, sliding_window, block_q, block_kv, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, segq_ref, segkv_ref,
         dq_ref, dq_s) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_s = refs
        segq_ref = segkv_ref = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off, kv_off = qi * block_q, ki * block_kv

    @pl.when(ki == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    run = _run_block(q_off, kv_off, block_q, block_kv, causal, sliding_window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        seg_q = segq_ref[0, 0] if segmented else None
        seg_kv = segkv_ref[0, 0] if segmented else None
        if causal or sliding_window is not None or segmented:
            s = s + _mask(q_off, kv_off, block_q, block_kv, causal,
                          sliding_window, seg_q, seg_kv)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_s[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, scale, causal, sliding_window, block_q, block_kv, group, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, segq_ref, segkv_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        segq_ref = segkv_ref = None

    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)
    q_off, kv_off = qi * block_q, ki * block_kv

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    run = _run_block(q_off, kv_off, block_q, block_kv, causal, sliding_window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        seg_q = segq_ref[0, 0] if segmented else None
        seg_kv = segkv_ref[0, 0] if segmented else None
        if causal or sliding_window is not None or segmented:
            s = s + _mask(q_off, kv_off, block_q, block_kv, causal,
                          sliding_window, seg_q, seg_kv)
        p = jnp.exp(s - lse[:, None])  # [bq, bkv]
        dv_s[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_s[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(gi == pl.num_programs(2) - 1,
                             qi == pl.num_programs(3) - 1))
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(
    scale, causal, sliding_window, block_q, block_kv, interpret,
    residuals, grads, delta=None, out_dtype=None,
):
    """``delta``/``out_dtype``: ring callers (parallel/ring.py) invoke this
    once per KV chunk inside a lax.scan — they precompute the loop-invariant
    delta = rowsum(do*o) once outside (XLA cannot CSE across scan
    iterations) and request fp32 gradients for cross-chunk accumulation."""
    q, k, v, o, lse, seg_q, seg_kv = residuals
    do = grads[0]
    b, n, sq, d = q.shape
    _, nkv, skv, _ = k.shape
    g = n // nkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)

    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
            keepdims=True
        )  # [b, n, sq, 1] — same tiled layout as lse

    segmented = seg_q is not None

    # ---- dq ----
    grid_dq = (b * n, sq // block_q, skv // block_kv)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        pl.BlockSpec((1, 1, block_kv, d), lambda bh, qi, ki: (bh // n, (bh % n) // g, ki, 0)),
        pl.BlockSpec((1, 1, block_kv, d), lambda bh, qi, ki: (bh // n, (bh % n) // g, ki, 0)),
        pl.BlockSpec((1, 1, block_q, d), lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh // n, 0, qi)),
            pl.BlockSpec((1, 1, block_kv), lambda bh, qi, ki: (bh // n, 0, ki)),
        ]
        args += [seg_q, seg_kv]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            sliding_window=sliding_window, block_q=block_q, block_kv=block_kv,
            segmented=segmented,
        ),
        grid=grid_dq,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, qi, ki: (bh // n, bh % n, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)

    # ---- dk, dv ----
    grid_dkv = (b * nkv, skv // block_kv, g, sq // block_q)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bh, ki, gi, qi: (bh // nkv, (bh % nkv) * g + gi, qi, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bh, ki, gi, qi: (bh // nkv, bh % nkv, ki, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bh, ki, gi, qi: (bh // nkv, bh % nkv, ki, 0)),
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bh, ki, gi, qi: (bh // nkv, (bh % nkv) * g + gi, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda bh, ki, gi, qi: (bh // nkv, (bh % nkv) * g + gi, qi, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda bh, ki, gi, qi: (bh // nkv, (bh % nkv) * g + gi, qi, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, ki, gi, qi: (bh // nkv, 0, qi)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda bh, ki, gi, qi: (bh // nkv, 0, ki)),
        ]
        args += [seg_q, seg_kv]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            sliding_window=sliding_window, block_q=block_q, block_kv=block_kv,
            group=g, segmented=segmented,
        ),
        grid=grid_dkv,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, ki, gi, qi: (bh // nkv, bh % nkv, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, ki, gi, qi: (bh // nkv, bh % nkv, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, out_dtype or k.dtype),
            jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)

    dsq = dskv = None
    return dq, dk, dv, dsq, dskv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, seg_q, seg_kv, scale, causal, sliding_window,
           block_q, block_kv, interpret):
    out, _ = _fwd(q, k, v, seg_q, seg_kv, scale, causal, sliding_window,
                  block_q, block_kv, interpret)
    return out


def _flash_fwd(q, k, v, seg_q, seg_kv, scale, causal, sliding_window,
               block_q, block_kv, interpret):
    out, lse = _fwd(q, k, v, seg_q, seg_kv, scale, causal, sliding_window,
                    block_q, block_kv, interpret)
    return out, (q, k, v, out, lse, seg_q, seg_kv)


def _flash_bwd(scale, causal, sliding_window, block_q, block_kv, interpret,
               residuals, g):
    dq, dk, dv, dsq, dskv = _bwd(
        scale, causal, sliding_window, block_q, block_kv, interpret,
        residuals, (g,),
    )
    return dq, dk, dv, dsq, dskv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _env_block(var: str, seq: int, cap: int = 1024) -> Optional[int]:
    """Sweep-only block-size override (tools/mfu_sweep.py retune rows).

    Ignored (with a one-line note — the override is process-wide, so a
    silently dropped value would make a sweep row measure the default)
    unless it
      * evenly divides ``seq`` — an override tuned for the bench shape must
        not break other call sites (e.g. a decode step with a different KV
        length) in the same process;
      * is a multiple of the minimum TPU tile (128 lanes; ADVICE r4 #2 — a
        non-tile value passes divisibility at some seqs and then dies as an
        opaque Mosaic compile error mid-sweep);
      * respects the same VMEM cap as :func:`_auto_block` (1024, or 512 at
        head_dim 256 — the caller passes the cap it would auto-pick under).
    """
    v = os.environ.get(var)
    if not v:
        return None
    blk = int(v)
    if blk % 128 != 0 or blk > cap or blk <= 0:
        # intrinsically invalid value: warn — silently measuring the
        # default mid-sweep is worse than the noise
        print(f"[flash_attention] ignoring {var}={blk} "
              f"(must be a positive multiple of 128 and <= VMEM cap {cap})",
              flush=True)
        return None
    if not (blk <= seq and seq % blk == 0):
        # by-design silent skip: an override tuned for the bench shape must
        # not break (or spam) other-seq call sites in the same process
        return None
    return blk


def _auto_block(seq: int, cap: int = 1024) -> int:
    """Largest power-of-two block <= cap dividing seq.

    Hardware sweep on TPU v5e (tools/tpu_kernel_check.py): 1024x1024 blocks
    are up to 2x faster than the old fixed 512 at seq >= 2048 (fewer grid
    iterations amortize the per-block mask/softmax bookkeeping), and within
    noise at seq 1024. VMEM at 1024x1024 fp32 scores is 4 MiB per score-
    sized intermediate — fine at head_dim 128, but the backward kernels keep
    ~4 such intermediates (s, p, dp, ds) plus q/k/v/do tiles, so the caller
    caps the block at 512 for head_dim 256 to stay inside the ~16 MiB/core
    VMEM budget.
    """
    for blk in (1024, 512, 256, 128):
        if blk <= cap and seq % blk == 0:
            return blk
    return seq


def pick_blocks(sq: int, skv: int, d: int) -> tuple:
    """THE block-size policy (VMEM cap by head_dim, sweep env overrides,
    auto fallback) — single source for flash_attention and the
    flash-in-ring path (parallel/ring.py), so MLT_FLASH_BLOCK_Q/KV sweeps
    apply to both and the cap never diverges.

    Measured (v5e, seq 8192, window 256): large KV blocks win even for
    small sliding windows — grid-iteration overhead outweighs the masked
    compute whole-tile pruning would save (1024x1024 98 ms vs 512x512
    109 ms vs 512x256 134 ms) — so no window-based cap."""
    cap = 1024 if d <= 128 else 512  # VMEM, see _auto_block
    block_q = (_env_block("MLT_FLASH_BLOCK_Q", sq, cap)
               or _auto_block(sq, cap))
    block_kv = (_env_block("MLT_FLASH_BLOCK_KV", skv, cap)
                or _auto_block(skv, cap))
    return block_q, block_kv


def flash_attention(
    q: jax.Array,  # [b, s, n, d]
    k: jax.Array,  # [b, s, nkv, d]
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,  # [b, s]
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] inputs."""
    b, sq, n, d = q.shape
    if not (k.shape[-1] == v.shape[-1] == d):
        raise ValueError(
            f"flash_attention needs one head width for q, k and v; got "
            f"{d}, {k.shape[-1]}, {v.shape[-1]} (latent attention's expanded "
            f"form takes the XLA path: ops/attention.attention)")
    auto_q, auto_kv = pick_blocks(sq, k.shape[1], d)
    if block_q is None:
        block_q = auto_q
    if block_kv is None:
        block_kv = auto_kv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = target_platform() == "cpu"
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    seg = (
        segment_ids.astype(jnp.int32)[:, None, :]
        if segment_ids is not None else None
    )
    out = _flash(qh, kh, vh, seg, seg, scale, causal, sliding_window,
                 block_q, block_kv, interpret)
    return out.transpose(0, 2, 1, 3)
