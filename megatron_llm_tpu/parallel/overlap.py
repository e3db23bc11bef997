"""Fine-grained compute/collective overlap: chunked collective matmuls.

Motivation (ROADMAP item 3; PAPERS.md "T3: Transparent Tracking &
Triggering for Fine-grained Overlap of Compute & Collectives"; the TPU
collective-matmul construction from "Overlap Communication with Dependent
Computation via Decomposition in Large Deep Learning Models"): the TP
collectives PR 6 introduced are emitted implicitly by XLA from sharding
constraints, as ONE all-reduce after each row-parallel contraction — the
interconnect sits idle while the GEMM runs, then the MXU sits idle while
the all-reduce runs.  This module makes the decomposition explicit so the
two pipelines overlap:

* **row-parallel** (attention ``dense``, ``fc2``; the contraction dim is
  tp-sharded) becomes a *reduce-scatter matmul ring*: the GEMM splits
  into ``tp`` output chunks inside a full-manual ``compat.shard_map``
  region; at every ring step the accumulator travels one hop
  (``ppermute``) WHILE the next chunk's partial product is computed —
  the two are data-independent, so XLA's latency-hiding scheduler runs
  the collective-permute DMA concurrently with the MXU work.  Under
  sequence parallelism the result stays seq-sharded (the reduce-scatter
  the reference hand-codes, layers.py:292); otherwise a tiled
  ``all_gather`` restores the replicated activation (together: the
  all-reduce, now pipelined against its own GEMM).
* **column-parallel + SP** (``qkv``, ``fc1`` on a seq-sharded residual
  stream) gets the mirrored *all-gather matmul ring*: each rank GEMMs
  the seq chunk it holds while ``ppermute`` brings in the next one.
  Without SP a column-parallel forward needs no communication, so there
  is nothing to overlap and the plain path is kept.

Ring schedule (row): rank ``q`` at step ``t`` computes the partial
product for output chunk ``c(q, t) = (q + tp - 1 - t) mod tp`` and adds
it to the accumulator in flight; accumulators move ``q -> q+1`` each
step, so after ``tp - 1`` hops rank ``r`` holds ``sum_q partial_q[chunk
r]`` — its own contribution added last, locally, in full precision.

Activation is a *trace-time* context (:func:`activate`): the train step
and the engine wrap their forward bodies, and the transformer sublayers
route row/column projections through :func:`row_parallel` /
:func:`column_parallel`, which fall back to the plain projection
whenever the context is inactive or the operand is ineligible
(quantized int8 / fp8 kernels, indivisible shapes).  ``--tp_overlap
off`` (the default) never enters the context at all — the forward is
byte-for-byte today's XLA-inserted-collective program.

Wire quantization (``--quantized_tp_collectives``, closing the PR 13
named follow-on): the row ring's in-flight accumulator chunks are int8
on the wire — symmetric absmax, one f32 scale per wire chunk, f32
scale applied on receipt, local partials accumulated in the compute
dtype (the EQuARX recipe of parallel/quantized.py applied to the
FORWARD collective).  Unlike the dp sync, a ring re-quantizes the
accumulator at every hop: a contribution entering at step ``t``
crosses ``tp - 1 - t`` hops and suffers one rounding ``<= scale/2``
per hop, so the worst-case element error is ``(tp - 1) * max_hop_scale
/ 2`` — bounded, and gated by tests/test_tp_overlap.py against the f32
ring.  The backward is a straight-through custom_vjp (gradients cross
the wire exactly, quantization is forward-only noise).

Why parity is a tolerance, not bitwise (unlike PR 11's ragged tick):
chunked-GEMM reduce-scatter REASSOCIATES the floating-point sum — the
plain path sums ``tp`` full partial products in one all-reduce; the
ring adds them one hop at a time interleaved with chunk GEMMs, and the
non-SP path additionally splits each GEMM row block at chunk
boundaries.  Same math, different association order, last-bits
different — so the contract is training loss rel <= 1e-4, engine
greedy tokens identical, per-token log-probs <= 5e-6 (bench_tp.py
overlap arm + tests/test_tp_overlap.py), while ``--tp_overlap off``
stays pinned bitwise.

The region is FULL-manual (``axis_names`` = every mesh axis), and overlap
is gated to pp == cp == 1 meshes: pipeline/ring-attention code owns its
own manual regions and the two must not nest.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from megatron_llm_tpu.core.parallel_state import (
    CP_AXIS,
    DATA_AXES,
    DP_AXIS,
    EP_AXIS,
    PP_AXIS,
    TP_AXIS,
)
from megatron_llm_tpu.parallel import compat

__all__ = [
    "OVERLAP_MODES",
    "OverlapParams",
    "overlap_mode",
    "overlap_params",
    "activate",
    "current",
    "row_parallel",
    "column_parallel",
    "vocab_parallel",
    "overlap_scope_name",
    "vocab_scope_name",
]

OVERLAP_MODES = ("off", "ring")

_EPS = 1e-20


class OverlapParams:
    """Resolved overlap decision for one (cfg, mesh) pair — everything the
    ring builders need, captured once so traced closures never re-read
    config state."""

    __slots__ = ("mesh", "tp", "data", "sequence_parallel", "quantized",
                 "ring_rows", "vocab_ring")

    def __init__(self, mesh: Mesh, tp: int, data: int,
                 sequence_parallel: bool, quantized: bool,
                 ring_rows: bool = True, vocab_ring: bool = False):
        self.mesh = mesh
        self.tp = tp
        self.data = data  # dp * ep (batch-dim divisor inside the region)
        self.sequence_parallel = sequence_parallel
        self.quantized = quantized
        # which rings this context enables (ISSUE 20): row/column layer
        # rings need a pp==cp==1 mesh (they nest no other manual region);
        # the vocab head ring runs OUTSIDE the pp region and so composes
        # with pipeline-parallel serving.
        self.ring_rows = ring_rows
        self.vocab_ring = vocab_ring

    def __repr__(self):
        return (f"OverlapParams(tp={self.tp}, sp={self.sequence_parallel}, "
                f"quantized={self.quantized}, ring_rows={self.ring_rows}, "
                f"vocab_ring={self.vocab_ring})")


def overlap_mode(cfg) -> str:
    """The configured ``--tp_overlap`` mode ('off' when absent)."""
    mode = getattr(cfg.parallel, "tp_overlap", "off") or "off"
    assert mode in OVERLAP_MODES, f"unknown --tp_overlap mode {mode!r}"
    return mode


def overlap_scope_name(tp: int) -> str:
    """The named scope stamped on ring HLO (and the tracer span name the
    engine emits per overlapped tick): ``forward-tp{N}-overlap``."""
    return f"forward-tp{tp}-overlap"


def vocab_scope_name(tp: int) -> str:
    """Named scope stamped on the vocab head ring's HLO:
    ``vocab-ring-tp{N}`` — the ppermute chain the bench and tests assert
    lives under this scope (mechanism checked, not assumed)."""
    return f"vocab-ring-tp{tp}"


def overlap_params(cfg, mesh: Optional[Mesh]) -> Optional["OverlapParams"]:
    """Resolve (cfg, mesh) to ring parameters, or None when overlap does
    not apply: no mesh, tp == 1 (single-chip degradation — the flags are
    silently inert), an fp8 forward (its GEMMs carry their own scaling
    protocol), or nothing enabled.  The row/column layer rings
    (``--tp_overlap ring``) additionally require a pp == cp == 1 layout
    (pipeline/ring-attention own manual regions the full-manual ring must
    not nest inside); the vocab head ring (``--vocab_ring``, ISSUE 20)
    runs outside the pp region so pp > 1 is allowed — only cp (which
    wraps the whole forward) excludes it."""
    if mesh is None:
        return None
    shape = dict(mesh.shape)
    tp = shape.get(TP_AXIS, 1)
    if tp <= 1:
        return None
    if getattr(cfg.model, "fp8", None) is not None:
        return None
    flat = shape.get(PP_AXIS, 1) == 1 and shape.get(CP_AXIS, 1) == 1
    ring_rows = overlap_mode(cfg) == "ring" and flat
    vocab_ring = (bool(getattr(cfg.parallel, "vocab_ring", False))
                  and shape.get(CP_AXIS, 1) == 1)
    if not (ring_rows or vocab_ring):
        return None
    data = shape.get(DP_AXIS, 1) * shape.get(EP_AXIS, 1)
    return OverlapParams(
        mesh, tp, data,
        bool(getattr(cfg.parallel, "sequence_parallel", False)),
        bool(getattr(cfg.parallel, "quantized_tp_collectives", False)),
        ring_rows=ring_rows, vocab_ring=vocab_ring,
    )


# ---------------------------------------------------------------------------
# Trace-time activation context
# ---------------------------------------------------------------------------


class _State(threading.local):
    def __init__(self):
        self.stack = []


_state = _State()


@contextlib.contextmanager
def activate(ovl: Optional[OverlapParams]):
    """Enable ring interception for code traced inside this block.

    Pure trace-time state (like ``jax.named_scope``): entering with None
    is a no-op, so callers write ``with overlap.activate(maybe_none):``
    unconditionally and the off mode costs nothing."""
    if ovl is None:
        yield
        return
    _state.stack.append(ovl)
    try:
        yield
    finally:
        _state.stack.pop()


def current() -> Optional[OverlapParams]:
    return _state.stack[-1] if _state.stack else None


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------


def _eligible_common(ovl: OverlapParams, p, x) -> bool:
    # a vocab_ring-only context does not intercept the layer projections
    if not ovl.ring_rows:
        return False
    # int8 weight-only trees carry kernel_q/kernel_scale (ops/quant.py) —
    # their dequant-inside-GEMM contract stays on the plain path
    if "kernel" not in p or getattr(x, "ndim", 0) != 3:
        return False
    if x.shape[0] % ovl.data:
        return False
    # a nested manual region (pipeline/ring-attention/qdp) must not wrap
    # another shard_map — the gate in overlap_params covers the config
    # cases, this covers direct callers inside foreign regions
    if not compat.get_abstract_mesh().empty:
        return False
    return True


def _row_eligible(ovl: OverlapParams, p, x) -> bool:
    if not _eligible_common(ovl, p, x):
        return False
    k = p["kernel"]
    if k.ndim != 2 or x.shape[-1] != k.shape[0] or k.shape[0] % ovl.tp:
        return False
    if ovl.sequence_parallel and x.shape[1] % ovl.tp:
        return False
    return True


def _col_eligible(ovl: OverlapParams, p, x) -> bool:
    if not _eligible_common(ovl, p, x):
        return False
    k = p["kernel"]
    if k.ndim not in (2, 3) or x.shape[-1] != k.shape[0]:
        return False
    if k.shape[-1] % ovl.tp or x.shape[1] % ovl.tp:
        return False
    return True


# ---------------------------------------------------------------------------
# The rings
# ---------------------------------------------------------------------------


def _ring_perm(tp: int):
    return tuple((i, (i + 1) % tp) for i in range(tp))


def _inv_perm(perm):
    return tuple((j, i) for i, j in perm)


def _quantized_wire_hop(perm):
    """int8 wire hop with straight-through gradients.

    Forward: quantize the accumulator chunk (symmetric absmax, one f32
    scale per wire chunk), ppermute the int8 payload + its scale,
    dequantize on receipt.  Backward: the exact inverse ppermute — the
    rounding is treated as forward-only noise (``jnp.round`` has a zero
    gradient, which would silently kill training; the straight-through
    rule keeps the wire differentiable and exact in the backward)."""

    def fwd_value(acc):
        a32 = acc.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(a32)) / 127.0, _EPS)
        q = jnp.clip(jnp.round(a32 / scale), -127.0, 127.0).astype(jnp.int8)
        q = jax.lax.ppermute(q, TP_AXIS, perm)
        scale = jax.lax.ppermute(scale, TP_AXIS, perm)
        return (q.astype(jnp.float32) * scale).astype(acc.dtype)

    @jax.custom_vjp
    def hop(acc):
        return fwd_value(acc)

    def hop_fwd(acc):
        return fwd_value(acc), None

    def hop_bwd(_, g):
        return (jax.lax.ppermute(g, TP_AXIS, _inv_perm(perm)),)

    hop.defvjp(hop_fwd, hop_bwd)
    return hop


def _wire_hop(ovl: OverlapParams):
    perm = _ring_perm(ovl.tp)
    if ovl.quantized:
        return _quantized_wire_hop(perm)
    return lambda acc: jax.lax.ppermute(acc, TP_AXIS, perm)


def _mod(c, tp: int):
    # jnp.mod follows the divisor's sign: non-negative for positive tp,
    # so (r - t) mod tp is a valid chunk index even when r < t
    return jnp.mod(c, tp)


def row_parallel(cfg, p, x, fallback: Callable[[Any, Any], Any]):
    """Row-parallel projection ([.., k] @ [k, n], k tp-sharded): the
    reduce-scatter matmul ring when overlap is active, else
    ``fallback(p, x)`` (the plain projection, byte for byte)."""
    ovl = current()
    if ovl is None or not _row_eligible(ovl, p, x):
        return fallback(p, x)
    mesh, tp = ovl.mesh, ovl.tp
    b, s, _ = x.shape
    kernel = p["kernel"]
    hop = _wire_hop(ovl)
    sp = ovl.sequence_parallel

    def body_sp(xl, wl):
        # xl [b/data, s, k/tp] -> acc [b/data, s/tp, n]: rank r finishes
        # holding seq chunk r fully reduced — the reduce-scatter result
        # the SP residual stream wants, no gather needed.
        wl = wl.astype(xl.dtype)
        r = jax.lax.axis_index(TP_AXIS)
        s_c = s // tp

        def chunk(c):
            return jax.lax.dynamic_slice_in_dim(xl, c * s_c, s_c, axis=1)

        acc = chunk(_mod(r + (tp - 1), tp)) @ wl
        for t in range(1, tp):
            acc = hop(acc) + chunk(_mod(r + (tp - 1 - t), tp)) @ wl
        return acc

    def body(xl, wl):
        # no SP: chunk the flattened [b_local * s] row block (pads to a
        # tp multiple so decode's s == 1 rows still chunk), ring-reduce,
        # then a tiled all_gather restores the replicated activation —
        # together, the all-reduce, pipelined against its own GEMM.
        wl = wl.astype(xl.dtype)
        r = jax.lax.axis_index(TP_AXIS)
        bl = xl.shape[0]
        rows = bl * s
        xf = xl.reshape(rows, xl.shape[-1])
        rows_c = -(-rows // tp)
        pad = rows_c * tp - rows
        if pad:
            xf = jnp.concatenate(
                [xf, jnp.zeros((pad, xf.shape[-1]), xf.dtype)])

        def chunk(c):
            return jax.lax.dynamic_slice_in_dim(xf, c * rows_c, rows_c,
                                                axis=0)

        acc = chunk(_mod(r + (tp - 1), tp)) @ wl
        for t in range(1, tp):
            acc = hop(acc) + chunk(_mod(r + (tp - 1 - t), tp)) @ wl
        y = jax.lax.all_gather(acc, TP_AXIS, axis=0, tiled=True)
        if pad:
            y = y[:rows]
        return y.reshape(bl, s, -1)

    out_spec = (P(DATA_AXES, TP_AXIS, None) if sp
                else P(DATA_AXES, None, None))
    with jax.named_scope(overlap_scope_name(tp)):
        y = compat.shard_map(
            body_sp if sp else body, mesh=mesh,
            in_specs=(P(DATA_AXES, None, TP_AXIS), P(TP_AXIS, None)),
            out_specs=out_spec,
            axis_names=set(mesh.axis_names), check_vma=False,
        )(x, kernel)
    if "bias" in p:
        # row-parallel bias is replicated and added post-reduce
        # (mappings.py:257 semantics — matches tp.py's spec rule)
        y = y + p["bias"].astype(y.dtype)
    return y


def column_parallel(cfg, p, x, fallback: Callable[[Any, Any], Any]):
    """Column-parallel projection ([.., h] @ [h, n], n tp-sharded) on a
    seq-sharded (SP) residual stream: the all-gather matmul ring.  Without
    SP a column-parallel forward has no collective to overlap, so the
    plain path is always kept."""
    ovl = current()
    if (ovl is None or not ovl.sequence_parallel
            or not _col_eligible(ovl, p, x)):
        return fallback(p, x)
    mesh, tp = ovl.mesh, ovl.tp
    b, s, _ = x.shape
    kernel = p["kernel"]
    perm = _ring_perm(tp)
    glu = kernel.ndim == 3  # GLU fc1 [h, 2, ffn]: tp shards the ffn axis

    def body(xl, wl):
        # xl [b/data, s/tp, h] (this rank's seq chunk), wl [h, n/tp].
        # GEMM the chunk in hand while ppermute brings in the next; each
        # arriving chunk lands at its own seq offset.
        wl2 = wl.reshape(wl.shape[0], -1).astype(xl.dtype)
        r = jax.lax.axis_index(TP_AXIS)
        bl, s_c, _ = xl.shape
        y = jnp.zeros((bl, s_c * tp, wl2.shape[-1]), xl.dtype)
        buf = xl
        y = jax.lax.dynamic_update_slice_in_dim(y, buf @ wl2, r * s_c,
                                                axis=1)
        for t in range(1, tp):
            buf = jax.lax.ppermute(buf, TP_AXIS, perm)
            c = _mod(r - t, tp)
            y = jax.lax.dynamic_update_slice_in_dim(y, buf @ wl2,
                                                    c * s_c, axis=1)
        if glu:
            return y.reshape(bl, s_c * tp, *wl.shape[1:])
        return y

    out_spec = (P(DATA_AXES, None, None, TP_AXIS) if glu
                else P(DATA_AXES, None, TP_AXIS))
    w_spec = P(None, None, TP_AXIS) if glu else P(None, TP_AXIS)
    with jax.named_scope(overlap_scope_name(tp)):
        y = compat.shard_map(
            body, mesh=mesh,
            in_specs=(P(DATA_AXES, TP_AXIS, None), w_spec),
            out_specs=out_spec,
            axis_names=set(mesh.axis_names), check_vma=False,
        )(x, kernel)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Vocab-parallel head ring (ISSUE 20)
# ---------------------------------------------------------------------------


def _vocab_eligible(ovl: OverlapParams, w, x) -> bool:
    if not ovl.vocab_ring:
        return False
    if getattr(x, "ndim", 0) != 3 or getattr(w, "ndim", 0) != 2:
        return False
    if x.shape[-1] != w.shape[0]:
        return False
    # each rank's vocab shard splits into tp sub-chunks: V % tp**2 == 0
    # (padded_vocab_size pads to a multiple of 128 * tp, so this holds
    # for every practical tp; tiny toy vocabs fall back)
    if w.shape[1] % (ovl.tp * ovl.tp):
        return False
    # never nest inside another manual region (the pp stage region in
    # particular: the head runs AFTER pipelined_transformer returns)
    if not compat.get_abstract_mesh().empty:
        return False
    return True


def vocab_parallel(cfg, w, x, fallback: Callable[[Any, Any], Any]):
    """Vocab-parallel head projection ([R, s, h] @ [h, V], V tp-sharded):
    the all-gather matmul ring when ``--vocab_ring`` is active, else
    ``fallback(w, x)`` (the plain GEMM + XLA-inserted all-gather).

    At serving time the head GEMM is the single largest collective per
    tick — the logits all-gather moves ``R * V`` elements EVERY decode
    step.  The ring decomposes each rank's ``[h, V/tp]`` shard into
    ``tp`` column sub-chunks: at step ``t`` the rank GEMMs sub-chunk
    ``t`` while the previously computed sub-chunks travel one hop
    (``ppermute``) — compute and wire are data-independent, so the
    latency-hiding scheduler overlaps them.  After ``2*tp - 2`` hops
    every rank holds all ``tp**2`` (owner, sub) blocks and assembles the
    replicated ``[R, s, V]`` logits.

    Unlike the row ring this does NOT reassociate any floating-point
    sum — the split is along output columns, the contraction dim stays
    intact, and the wire is never quantized — but XLA may still tile the
    sub-GEMMs differently from the fused one, so the contract is the
    tolerance one (greedy tokens identical, log-probs <= 5e-6), not
    bitwise.
    """
    ovl = current()
    if ovl is None or not _vocab_eligible(ovl, w, x):
        return fallback(w, x)
    mesh, tp = ovl.mesh, ovl.tp
    R, s, h = x.shape
    V = w.shape[1]
    u = V // (tp * tp)  # sub-chunk width (vc = V/tp per rank, tp subs)
    perm = _ring_perm(tp)

    def body(xl, wl):
        # xl [R, s, h] replicated, wl [h, V/tp] this rank's column shard.
        wl = wl.astype(xl.dtype)
        r = jax.lax.axis_index(TP_AXIS)
        rows = R * s
        xf = xl.reshape(rows, h)
        # y4[o, j] = owner o's sub-chunk j — assembled as blocks arrive.
        y4 = jnp.zeros((tp, tp, rows, u), xl.dtype)
        live = {}  # sub index -> in-flight block (computed at step j)
        for t in range(2 * tp - 1):
            # 1) hop everything in flight: ONE ppermute on the stacked
            #    payload (sub j has hopped t - j times after this)
            if live:
                js = sorted(live)
                payload = jnp.stack([live[j] for j in js])
                payload = jax.lax.ppermute(payload, TP_AXIS, perm)
                for i, j in enumerate(js):
                    live[j] = payload[i]
            # 2) GEMM sub-chunk t locally — data-independent of the hop
            #    above, so the DMA hides behind this MXU work
            if t < tp:
                live[t] = xf @ jax.lax.dynamic_slice_in_dim(
                    wl, t * u, u, axis=1)
            # 3) place every in-flight block: after ``t - j`` hops rank r
            #    holds owner ``(r - (t - j)) mod tp``'s sub j
            for j in list(live):
                hops = t - j
                o = _mod(r - hops, tp)
                y4 = jax.lax.dynamic_update_slice(
                    y4, live[j][None, None], (o, jnp.int32(j), 0, 0))
                if hops == tp - 1:  # visited every rank — done
                    del live[j]
        # owner-major (o, j, u) block order == global column order
        return y4.transpose(2, 0, 1, 3).reshape(R, s, V)

    with jax.named_scope(vocab_scope_name(tp)):
        return compat.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, TP_AXIS)),
            out_specs=P(),
            axis_names=set(mesh.axis_names), check_vma=False,
        )(x, w)
