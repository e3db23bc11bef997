"""Pallas TPU flash attention (FlashAttention-2 style), fwd + bwd.

Replaces the reference's external FlashAttention-2 CUDA dependency
(transformer.py:9,518-600: flash_attn_func with causal, GQA, sliding-window)
and the fused scaled-masked-softmax CUDA kernels (fused_kernels/, subsumed —
the softmax never materializes).

Design (blockwise online softmax, one pass over KV per Q block):

* layout [b, heads, seq, head_dim]; grid (b*n, live (Q block, KV block)
  pairs) with the KV block innermost — on TPU the grid is a sequential
  loop, so VMEM scratch (running max m, normalizer l, fp32 accumulator)
  carries across KV iterations for a fixed Q block.
* GQA native: K/V keep n_kv heads; the Q-head grid index maps to kv head
  ``h // group`` in the BlockSpec index map — no broadcast-expand (the
  reference expands K/V at transformer.py:459-466).
* causal + sliding-window + segment-id masking via broadcasted iota on
  *global* positions. The grid follows the STATIC mask (``live_blocks``):
  a block pair causal / window kill is no grid step and copies nothing, a
  pair they leave whole adds no mask; the list reaches the index maps and
  the kernel as one scalar-prefetch operand.
* backward: two kernels (dq; dk/dv fused) recomputing p from the saved
  logsumexp — the standard flash-2 residual scheme (saves q,k,v,o,lse).

Numerics: logits and softmax in fp32 (matches attention_softmax_in_fp32 +
the XLA fallback in ops/attention.py); accumulators fp32; outputs cast to the
input dtype.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# compile-TARGET platform: AOT lowering for a TPU topology on a CPU
# host must compile the real kernel, not interpret mode
from megatron_llm_tpu.core.parallel_state import target_platform
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# The live blocks of a static mask
# ---------------------------------------------------------------------------

# an entry is one int32 word (the lists ride in SMEM, 1 MiB a core, and a
# rectangle of 127 x 127 blocks under a group of 7 has 112,903 of them):
# its kind in the low two bits, whether it is the first / last entry of its
# outer block, (dkv) the group member, the inner block, the outer block
DEAD, WHOLE, CUT = 0, 1, 2
KIND = 3
FIRST, LAST = 4, 8
MEMBER_SHIFT, INNER_SHIFT, OUTER_SHIFT = 4, 12, 22
MEMBER_MAX, BLOCK_MAX = 255, 1023


def _member(word):
    return (word >> MEMBER_SHIFT) & MEMBER_MAX


def _inner(word):
    return (word >> INNER_SHIFT) & BLOCK_MAX


def _outer(word):
    return (word >> OUTER_SHIFT) & BLOCK_MAX  # the mask drops the sign's copies


class LiveBlocks(NamedTuple):
    """The grid steps of one flash kernel, in the order it accumulates them
    (:func:`live_blocks`): entry ``t`` visits block ``_inner(steps[t])`` of
    the reduced axis for block ``_outer(steps[t])`` of the kept one."""

    steps: np.ndarray  # int32 [steps], packed as above
    total: int         # block positions of the whole rectangle
    live: int          # of them, those the mask leaves a pair in
    cut: int           # of the live ones, those the mask crosses

    @property
    def kinds(self) -> Tuple[int, ...]:
        return tuple(sorted(set((self.steps & KIND).tolist())))


@functools.lru_cache(maxsize=None)
def live_blocks(
    sq: int, skv: int, block_q: int, block_kv: int, causal: bool,
    sliding_window: Optional[int], outer: str = "q", members: int = 1,
) -> LiveBlocks:
    """The (outer block, inner block) pairs the STATIC mask leaves alive.

    What is alive follows from the shapes and the mask alone (segment ids
    are data and are masked inside the blocks), so it is decided here, at
    trace time, and the kernels' grids walk this list: a block the mask
    kills is never a grid step and none of its operands is copied.  Query
    position ``i`` sees key ``j`` where ``i >= j`` (causal) and
    ``i - j < sliding_window``; over a block pair ``i - j`` takes every
    value from ``q_first - kv_last`` to ``q_last - kv_first``, so a pair is
    live where that range meets the allowed one, WHOLE where it lies inside
    it (the step adds no mask) and CUT otherwise.

    ``outer`` names the axis whose block keeps the accumulators: ``"q"``
    (forward and dq: the key blocks of a query block in rising order) or
    ``"kv"`` (dkv: under each key block every one of the ``members`` query
    heads of its group in turn, the query blocks innermost).  An outer
    block with no live pair still gets one DEAD step: its output has to be
    written (zeros), and no kernel computes in it.
    """
    q_first = np.arange(sq // block_q)[:, None] * block_q
    kv_first = np.arange(skv // block_kv)[None, :] * block_kv
    low = q_first - (kv_first + block_kv - 1)   # least i - j of the pair
    high = (q_first + block_q - 1) - kv_first   # greatest
    live = np.ones(low.shape, bool)
    whole = np.ones(low.shape, bool)
    if causal:
        live &= high >= 0
        whole &= low >= 0
    if sliding_window is not None:
        live &= low < sliding_window
        whole &= high < sliding_window
    kind = np.where(live, np.where(whole, WHOLE, CUT), DEAD)
    if outer == "kv":
        kind = kind.T
    if max(kind.shape) > BLOCK_MAX + 1 or members > MEMBER_MAX + 1:
        raise ValueError(
            f"flash attention walks at most {BLOCK_MAX + 1} blocks a side and "
            f"{MEMBER_MAX + 1} query heads a KV head; got {kind.shape} blocks "
            f"of ({block_q}, {block_kv}) and a group of {members}")
    steps = []
    for o, row in enumerate(kind):
        inner = np.flatnonzero(row)
        if not inner.size:
            steps.append(o << OUTER_SHIFT | DEAD | FIRST | LAST)
            continue
        walk = [o << OUTER_SHIFT | int(i) << INNER_SHIFT | m << MEMBER_SHIFT
                | int(row[i]) for m in range(members) for i in inner]
        walk[0] |= FIRST
        walk[-1] |= LAST
        steps.extend(walk)
    return LiveBlocks(np.asarray(steps, np.uint32).view(np.int32), kind.size,
                      int(live.sum()), int((live & ~whole).sum()))


def _grid_spec(blocks: LiveBlocks, heads: int, **specs):
    """Grid (heads, steps of ``blocks``); the list reaches the index maps
    and the kernel as one scalar-prefetch operand."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(heads, len(blocks.steps)), **specs)


def _query_walk_maps(n: int, g: int):
    """Index maps of a walk whose outer block is the query's (forward and
    dq; grid axis 0 runs over batch x the ``n`` query heads, ``g`` of them a
    KV head): the q-side tile, the kv-side tile, the two segment-id rows."""
    def q_map(bh, t, steps):
        return bh // n, bh % n, _outer(steps[t]), 0

    def kv_map(bh, t, steps):
        return bh // n, (bh % n) // g, _inner(steps[t]), 0

    def segq_map(bh, t, steps):
        return bh // n, 0, _outer(steps[t])

    def segkv_map(bh, t, steps):
        return bh // n, 0, _inner(steps[t])

    return q_map, kv_map, segq_map, segkv_map


def _walk(entry, kinds, init, step, finish):
    """One grid step of a kernel at the list's word ``entry``: ``init`` on
    the first entry of an outer block, ``step(cut)`` with or without the
    static mask as the entry says, ``finish`` on the last.  ``kinds`` is
    what the list holds at all: a branch no entry takes is not built, and
    a list of one kind (no mask: whole blocks alone) branches on nothing."""
    pl.when(entry & FIRST != 0)(init)
    if len(kinds) == 1:
        step(kinds == (CUT,))
    else:
        for kind in (WHOLE, CUT):
            if kind in kinds:
                pl.when(entry & KIND == kind)(
                    functools.partial(step, kind == CUT))
    pl.when(entry & LAST != 0)(finish)


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


def _masked(s, cut, q_off, kv_off, causal, sliding_window, segq_ref,
            segkv_ref):
    """Scores ``s`` of one block under the static mask (a CUT block only:
    a whole one has nothing to mask) and the segment ids (every block of a
    segmented call: they are data)."""
    if not cut:
        causal, sliding_window = False, None
    if not (causal or sliding_window is not None or segq_ref is not None):
        return s
    seg_q = segq_ref[0, 0] if segq_ref is not None else None
    seg_kv = segkv_ref[0, 0] if segkv_ref is not None else None
    return s + _mask(q_off, kv_off, *s.shape, causal, sliding_window,
                     seg_q, seg_kv)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    # scalar prefetch: the live list; then refs (segment refs present only
    # when segmented)
    steps_ref, *refs,
    scale: float,
    causal: bool,
    sliding_window: Optional[int],
    block_q: int,
    block_kv: int,
    kinds: Tuple[int, ...],
    segmented: bool,
):
    if segmented:
        q_ref, k_ref, v_ref, segq_ref, segkv_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        segq_ref = segkv_ref = None

    entry = steps_ref[pl.program_id(1)]
    q_off = _outer(entry) * block_q
    kv_off = _inner(entry) * block_kv

    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _step(cut):
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]
        s = _masked(s, cut, q_off, kv_off, causal, sliding_window,
                    segq_ref, segkv_ref)

        m_prev = m_s[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        # guard rows that are fully masked SO FAR (m_cur still -inf — happens
        # under sliding window when a row's first live block holds none of
        # its keys): exp(-inf - -inf) would be 1, poisoning the accumulator.
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur[:, None]))
        l_cur = alpha * l_s[:, 0] + jnp.sum(p, axis=1)
        m_s[:, 0] = m_cur
        l_s[:, 0] = l_cur
        v = v_ref[0, 0].astype(jnp.float32)
        acc_s[:] = acc_s[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _finish():
        l = l_s[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_s[:] / l_safe[:, None]).astype(o_ref.dtype)
        # trailing singleton keeps the (sublane, lane) tile legal on TPU
        lse_ref[0, 0, :, 0] = (m_s[:, 0] + jnp.log(l_safe)).astype(jnp.float32)

    _walk(entry, kinds, _init, _step, _finish)


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
    blocks = live_blocks(sq, skv, block_q, block_kv, causal, sliding_window)
    q_map, kv_map, segq_map, segkv_map = _query_walk_maps(n, g)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
    ]
    args = [q, k, v]
    segmented = seg_q is not None
    if segmented:
        # [b, 1, s] layout: the unit middle dim keeps the block's
        # second-to-last dimension equal to the array's (TPU tiling rule)
        in_specs += [
            pl.BlockSpec((1, 1, block_q), segq_map),
            pl.BlockSpec((1, 1, block_kv), segkv_map),
        ]
        args += [seg_q, seg_kv]

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sliding_window=sliding_window,
        block_q=block_q, block_kv=block_kv, kinds=blocks.kinds,
        segmented=segmented,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            blocks, b * n,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_map),
                pl.BlockSpec((1, 1, block_q, 1), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, n, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(jnp.asarray(blocks.steps), *args)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    steps_ref, *refs,
    scale, causal, sliding_window, block_q, block_kv, kinds, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, segq_ref, segkv_ref,
         dq_ref, dq_s) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_s = refs
        segq_ref = segkv_ref = None

    entry = steps_ref[pl.program_id(1)]
    q_off, kv_off = _outer(entry) * block_q, _inner(entry) * block_kv

    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    def _step(cut):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _masked(s, cut, q_off, kv_off, causal, sliding_window,
                    segq_ref, segkv_ref)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_s[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)

    _walk(entry, kinds, _init, _step, _finish)


def _bwd_dkv_kernel(
    steps_ref, *refs,
    scale, causal, sliding_window, block_q, block_kv, kinds, segmented,
):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, segq_ref, segkv_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        segq_ref = segkv_ref = None

    entry = steps_ref[pl.program_id(1)]
    q_off, kv_off = _inner(entry) * block_q, _outer(entry) * block_kv

    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _step(cut):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = _masked(s, cut, q_off, kv_off, causal, sliding_window,
                    segq_ref, segkv_ref)
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

    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)

    _walk(entry, kinds, _init, _step, _finish)


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
    static = dict(scale=scale, causal=causal, sliding_window=sliding_window,
                  block_q=block_q, block_kv=block_kv, segmented=segmented)

    # ---- dq: a query block's live key blocks ----
    blocks = live_blocks(sq, skv, block_q, block_kv, causal, sliding_window)
    q_map, kv_map, segq_map, segkv_map = _query_walk_maps(n, g)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
        pl.BlockSpec((1, 1, block_kv, d), kv_map),
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_q, 1), q_map),
        pl.BlockSpec((1, 1, block_q, 1), q_map),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), segq_map),
            pl.BlockSpec((1, 1, block_kv), segkv_map),
        ]
        args += [seg_q, seg_kv]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kinds=blocks.kinds, **static),
        grid_spec=_grid_spec(
            blocks, b * n,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(jnp.asarray(blocks.steps), *args)

    # ---- dk, dv: a key block's live query blocks, under each of the g
    # query heads of its group in turn ----
    blocks = live_blocks(sq, skv, block_q, block_kv, causal, sliding_window,
                         outer="kv", members=g)

    def member_map(bh, t, steps):
        head = (bh % nkv) * g + _member(steps[t])
        return bh // nkv, head, _inner(steps[t]), 0

    def key_map(bh, t, steps):
        return bh // nkv, bh % nkv, _outer(steps[t]), 0

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), member_map),
        pl.BlockSpec((1, 1, block_kv, d), key_map),
        pl.BlockSpec((1, 1, block_kv, d), key_map),
        pl.BlockSpec((1, 1, block_q, d), member_map),
        pl.BlockSpec((1, 1, block_q, 1), member_map),
        pl.BlockSpec((1, 1, block_q, 1), member_map),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, t, steps: (bh // nkv, 0, _inner(steps[t]))),
            pl.BlockSpec((1, 1, block_kv),
                         lambda bh, t, steps: (bh // nkv, 0, _outer(steps[t]))),
        ]
        args += [seg_q, seg_kv]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, kinds=blocks.kinds, **static),
        grid_spec=_grid_spec(
            blocks, b * nkv,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_kv, d), key_map),
                pl.BlockSpec((1, 1, block_kv, d), key_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, d), jnp.float32),
                pltpu.VMEM((block_kv, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, out_dtype or k.dtype),
            jax.ShapeDtypeStruct(v.shape, out_dtype or v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(jnp.asarray(blocks.steps), *args)

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

    Measured (v5e, PR 49: one bf16 sequence, fwd + dq + dkv together, the
    grids walking live blocks only, so a dead block costs nothing): the
    large block still wins under a window of a few blocks, though a fifth
    of its live pairs are masked — seq 16384, 28 heads on 4, window 4096:
    1024x1024 29.9 ms, 512x1024 31.7, 512x512 38.0, 1024x512 38.5 (causal
    alone 57.4 against 78.7 at 512x512): a step's fixed cost and its
    operands' copies outweigh the masked compute. Only a window far under
    a block turns it — seq 8192, 16 heads, window 256: 512x512 2.75 ms,
    256x256 3.12, 1024x1024 3.77 (while a dead block still cost its copies
    the same three read 7.67, 17.0 and 5.86, which is what this said
    before) — and no preset or cell has such a window, so no window-based
    cap (tools/tpu_kernel_check.py --time prints these)."""
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
