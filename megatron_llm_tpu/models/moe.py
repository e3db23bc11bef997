"""Mixture-of-Experts layer with expert parallelism — beyond-reference feature.

The reference (xingyaoww/Megatron-LLM) has **no MoE**: its parallel_state.py
carves only TP/PP/DP/embedding groups (SURVEY §2.1 "EP: absent"). This module
adds the capability TPU-first, in the GShard/Switch/Mixtral lineage:

* **Routing** is a dense top-k softmax gate computed in fp32 with a
  load-balancing auxiliary loss (Switch Transformer) and an optional router
  z-loss (ST-MoE) — both standard published formulations.
* **Dispatch/combine are einsums** against one-hot capacity tensors — no
  scatter/gather, no dynamic shapes, so everything lands on the MXU and the
  all-to-all between data- and expert-sharded layouts is *inferred by XLA*
  from sharding constraints (the same way our TP all-reduces replace NCCL
  calls, parallel/tp.py).
* **Expert parallelism is a mesh axis** (``ep``, carved out of dp — the
  ep | dp convention Megatron-LM upstream uses): expert weight stacks
  [E, ...] shard their expert axis over ``ep``, dispatched activations are
  sharding-constrained from batch-sharded [G:(dp,ep), T, h] to
  expert-sharded [G:dp, E:ep, C, h], and XLA emits the all-to-all over the
  ICI ring. TP composes: the per-expert FFN hidden axis shards over ``tp``
  exactly like the dense MLP (column- then row-parallel, parallel/tp.py).
* **Capacity-based token dropping** (the ``ep > 1`` and expert-choice
  path only): each expert processes at most
  C = ceil(topk * T * capacity_factor / E) tokens per group; overflow tokens
  fall through to the residual stream (their combine weight is zero), which
  keeps every shape static for XLA.
* **Dropless dispatch** (:func:`dropless_experts`; token-choice routing at
  ``ep == 1``, trainer and serving engine alike): the ``T x topk``
  assignments are sorted by expert, the sorted rows go through ONE grouped
  GEMM for fc1 and one for fc2 (:func:`grouped_matmul`, group sizes = rows
  an expert), and the weighted results are gathered back to their tokens.
  ``T x topk`` is static, so every shape is; no token is dropped at any
  load, no ``[G, T, E, C]`` one-hot exists (at 256 experts that tensor
  would be the layer), and ``moe_capacity_factor`` / ``moe_group_size`` do
  not apply.
* **A layer told which experts it holds** (``moe_experts_held``,
  ``moe_first_held_expert``: one chip's share of an expert-parallel layer
  whose other chips are not here).  The router keeps its ``num_experts``
  outputs and its top-k and the losses count all of them; the expert stacks
  hold the held experts only; the dispatch keeps the assignments whose
  expert is held and drops the rest BEFORE the gather, so absent experts
  cost no rows, no GEMM and no multiplied zeros; the output is the held
  experts' part of the layer's sum.  The row buffer is static:
  ``moe_capacity_factor`` x the held share of the ``T x topk`` assignments
  (at most all of them), and an assignment that finds it full is dropped
  and COUNTED (``aux[5]``), never silently.  Every call works the WHOLE
  buffer: a short one for the calls whose held assignments fit it (a
  ``jax.lax.cond`` over two row counts, equal bits) was built and
  measured in PR 58 and gave back inside its branch what it saved the
  dispatch, at 6 s of set-up (PERF.md, section 6).
* **The router's options are data of the family** (:func:`route`):
  softmax or sigmoid scores, a selection bias that picks the top-k and is
  not part of the weight, renormalisation, a scaling factor; and
  **shared experts** every token takes (one dense MLP of their summed
  widths, whose output is their sum, or their average where the family
  says ``moe_shared_combination`` 'average').  A share of the experts and
  shared experts go together where attention is data-parallel: each chip
  computes the shared experts for its own tokens, once.

Parameter schema (per layer; stacked on a leading layer axis under scan):

    {'router':  {'kernel': [h, E], 'bias'?: [E]}          # fp32, replicated
     'experts': {'fc1': {'kernel': [E, 2, h, ffn] | [E, ffn, h], 'bias'?},
                 'fc2': {'kernel': [E, ffn, h], 'bias'?}},
     'shared'?: {'fc1': {'kernel': [h, 2, S*ffn]}, 'fc2': {'kernel': [S*ffn, h]}}}

``router.bias`` is the selection bias (``e_score_correction_bias``); ``ffn``
is the expert width (``moe_ffn_hidden_size``, default ``ffn_hidden_size``).
A GLU expert's fc1 holds its value half at ``[:, 0]`` and its gated half at
``[:, 1]``, each a whole ``[h, ffn]`` matrix: the dense MLP's ``[h, 2, ffn]``
with the chunk axis moved out, so that ``[E * 2, h, ffn]`` is a view of the
stack and the grouped kernel reads it where it lies (as ``[E, h, 2, ffn]``
the leaf is tiled in pairs and had to be laid out anew before every call:
PERF.md, PR 31).  tp still shards the last axis.  An expert that is NOT
gated (no GLU: ``relu(.)^2``, gelu) keeps its fc1 as ``[ffn, h]``, the way
its fc2 lies: ``ffn`` is then never a kernel operand's minor axis, which an
expert width off the 128-lane grid (Nemotron-3's 1,856) would have XLA lay
out anew, the whole stack, before every call (3.5 GB a tick at 23 x 16 x
2688 x 1856: found compiling for a v5e without a chip, PR 53); the grouped
GEMM reads it with ``transposed`` and tp shards its ffn axis, the
second-last.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.ops.activations import get_mlp_activation, glu_product

Params = Dict[str, Any]


def moe_capacity(cfg, tokens_per_group: int) -> int:
    """Expert capacity C for one routing group of T tokens (token-choice:
    ceil(topk * T * cf / E), GShard convention)."""
    m = cfg.model
    cap = int(-(-m.moe_router_topk * tokens_per_group * m.moe_capacity_factor
                // m.num_experts))  # ceil
    return max(cap, m.moe_min_capacity)


def moe_capacity_expert_choice(cfg, tokens_per_group: int) -> int:
    """Expert-choice capacity: ceil(T * cf / E) (Zhou et al. definition —
    no topk factor; that knob is token-choice-only), clamped to T because
    an expert cannot select more tokens than the group holds."""
    m = cfg.model
    cap = int(-(-tokens_per_group * m.moe_capacity_factor // m.num_experts))
    return min(max(cap, m.moe_min_capacity), tokens_per_group)


# the per-layer aux vector: (load-balance loss, router z-loss) for the
# trained loss, then what the router did, for the serving engine's counters
# (generation/ragged.py) and the trainer's `train-moe` span: assignments
# made (rows x topk), distinct experts that received at least one row,
# assignments whose expert is held here and ran, held assignments dropped
# because the row buffer was full, and distinct HELD experts that received
# at least one row
AUX_LEN = 7


def expert_width(cfg) -> int:
    m = cfg.model
    return m.moe_ffn_hidden_size or m.ffn_hidden_size


def init_moe_params(cfg, key: jax.Array) -> Params:
    m = cfg.model
    # the router keeps every output; the stacks hold the held experts
    h, f, e = m.hidden_size, expert_width(cfg), m.experts_held
    glu = m.glu_activation is not None
    std = m.init_method_std
    out_std = std / (2.0 * m.num_layers) ** 0.5 if m.use_scaled_init_method else std
    kr, k1, k2 = jax.random.split(key, 3)
    # per-expert independent init: one key per expert, same distribution as
    # the dense MLP (transformer.init_layer_params)
    fc1_shape = (e, 2, h, f) if glu else (e, f, h)
    p: Params = {
        "router": {"kernel": std * jax.random.normal(
            kr, (h, m.num_experts), jnp.float32)},
        "experts": {
            "fc1": {"kernel": std * jax.random.normal(k1, fc1_shape, jnp.float32)},
            "fc2": {"kernel": out_std * jax.random.normal(k2, (e, f, h), jnp.float32)},
        },
    }
    if m.use_bias:
        p["experts"]["fc1"]["bias"] = jnp.zeros((e, 2, f) if glu else (e, f),
                                                jnp.float32)
        p["experts"]["fc2"]["bias"] = jnp.zeros((e, h), jnp.float32)
    if m.moe_selection_bias:
        # a trained checkpoint holds what the balance update left there: it
        # moves by a fixed step while an expert is over- or under-loaded, so
        # the values are small beside the scores (std 0.02 against a sigmoid
        # score's ~0.2) and yet decide the top-k, whose neighbours lie
        # ~0.003 apart.  Zeros would make every test and benchmark blind to
        # a program that forgets the bias; large values would skew which
        # experts a tick touches, which a balanced model does not
        p["router"]["bias"] = 0.02 * jax.random.normal(
            jax.random.fold_in(kr, 1), (m.num_experts,), jnp.float32)
    if m.moe_shared_experts:
        fs = f * m.moe_shared_experts
        ks1, ks2 = jax.random.split(jax.random.fold_in(key, 1))
        p["shared"] = {
            "fc1": {"kernel": std * jax.random.normal(
                ks1, (h, 2, fs) if glu else (h, fs), jnp.float32)},
            "fc2": {"kernel": out_std * jax.random.normal(
                ks2, (fs, h), jnp.float32)},
        }
    return p


def _expert_kernel(p_lin: Params, dt) -> Tuple[jax.Array, Any]:
    """Expert weight + optional int8 per-channel scale (the shared
    quantized-leaf contract, ops/quant.py:resolve_kernel)."""
    from megatron_llm_tpu.ops.quant import resolve_kernel

    return resolve_kernel(p_lin, dt)


def _ep_constraint(x: jax.Array, expert_axis: int) -> jax.Array:
    """Constrain an [G, E, C, ...] dispatched tensor so G rides dp and E rides
    ep — the boundary where XLA inserts the data<->expert all-to-all."""
    from megatron_llm_tpu.core import parallel_state as ps
    from jax.sharding import PartitionSpec as P

    if not ps.mesh_is_initialized():
        return x
    mesh = ps.get_global_mesh()
    if ps.EP_AXIS not in mesh.shape:
        return x
    spec = [None] * x.ndim
    spec[0] = ps.DP_AXIS
    spec[expert_axis] = ps.EP_AXIS
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _router_z_loss(router_logits: jax.Array) -> jax.Array:
    """ST-MoE router z-loss: mean(logsumexp(logits)^2) keeps logits small."""
    return jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)


def _aux(balance, z, rows_per_expert: jax.Array) -> jax.Array:
    """The layer's aux vector [AUX_LEN] from its two losses and the rows
    each expert received."""
    total = rows_per_expert.sum().astype(jnp.float32)
    # held and dropped: the dispatch fills them in where it keeps a share
    return jnp.stack([
        balance.astype(jnp.float32), z.astype(jnp.float32), total,
        (rows_per_expert > 0).sum().astype(jnp.float32), total,
        jnp.zeros((), jnp.float32),
        (rows_per_expert > 0).sum().astype(jnp.float32)])


@jax.named_scope("router")
def route(cfg, p_router: Params, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Token-choice routing of ``x`` [T, h], in float32 whatever the
    compute dtype.  The family's options (config/arguments.py):

    * ``moe_score_func``: ``softmax`` over the experts or ``sigmoid`` of
      each logit;
    * ``moe_selection_bias``: ``router.bias`` is added to the scores to
      PICK the top-k (``e_score_correction_bias``); the weights are the
      scores without it;
    * ``moe_normalize_gates``: the chosen weights are divided by their sum
      (+ 1e-20, as the DeepSeek-V3 modelling code has it);
    * ``moe_routed_scaling_factor`` multiplies them.

    Returns (experts [T, K] int32, weights [T, K] float32, rows an expert
    received [E] float32, aux [AUX_LEN])."""
    m = cfg.model
    e_, k_ = m.num_experts, m.moe_router_topk
    logits = x.astype(jnp.float32) @ p_router["kernel"].astype(jnp.float32)
    if m.moe_score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    pick = scores
    if "bias" in p_router:
        pick = scores + p_router["bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(_group_limited(m, pick), k_)     # [T, K]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if m.moe_normalize_gates:
        w = w / (w.sum(-1, keepdims=True) + m.moe_gate_eps)
    w = w * m.moe_routed_scaling_factor
    # rows an expert received: compares, no scatter ([T*K, E] booleans)
    counts = (idx.reshape(-1, 1) == jnp.arange(e_)[None, :]).sum(
        0).astype(jnp.float32)
    # load-balance loss (Switch eq. 4 generalised to top-k): share of the
    # assignments an expert received x its mean (normalised) score, x E
    probs = scores / scores.sum(-1, keepdims=True)
    balance = e_ * jnp.sum(counts / counts.sum() * probs.mean(0))
    return idx, w, counts, _aux(balance, _router_z_loss(logits), counts)


class StackedExperts(NamedTuple):
    """``experts`` of every layer of a stack (leaves ``[L, E, ...]``) and
    the layer whose experts are meant: what the serving tick hands the
    dispatch instead of a scanned slice (models/transformer.py
    ``transformer_forward``)."""

    stack: Params
    layer: jax.Array


_GMM_ROWS = 128            # rows of one tile of the grouped kernel ...
_GMM_ROWS_MANY = 512       # ... and where an expert has at least as many
_GMM_WEIGHT_TILE = 3 << 20  # bytes of one expert-weight tile in VMEM
_GMM_GRAD_TILE = 1 << 19   # elements of one weight-gradient tile (float32)


def _tile(dim: int, cap: int) -> int:
    """``dim`` where it is on the 128 grid and ``cap`` holds it; else the
    largest multiple of 128 that divides ``dim`` and is at most ``cap``;
    where none does (a width off the grid: 1,856), the largest multiple of
    128 up to ``cap`` and no more than covers ``dim``: the kernel cuts its
    last tile."""
    if dim <= cap and dim % 128 == 0:
        return dim
    whole = [t for t in range(128, cap + 1, 128) if dim % t == 0]
    return max(whole) if whole else min(cap // 128 * 128,
                                        -(-dim // 128) * 128)


def _gmm_tiles(tm: int, k: int, n: int, itemsize: int):
    """Tiles of ``[m, k] @ [k, n]``: the whole k if 2048 hold it, then as
    much of n as the weight tile's bytes allow."""
    tk = _tile(k, 2048)
    return tm, tk, _tile(n, max(128, _GMM_WEIGHT_TILE // (tk * itemsize)
                                // 128 * 128))


def _row_tile(m: int, groups: int) -> int:
    """512 rows a tile where the groups average at least that, else 128."""
    return _GMM_ROWS_MANY if m % _GMM_ROWS_MANY == 0 and (
        m // groups >= _GMM_ROWS_MANY) else _GMM_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(rows, kernel, sizes, first=None, transposed=False):
    """megablox ``gmm`` with tiles chosen per problem in the backward too
    (jax's own vjp reuses the forward's tiles on the transposed shapes).
    ``transposed``: the ``W[e]`` lie as ``[n, k]``.  ``first`` (a scalar,
    the serving tick's stack): ``sizes`` are the groups ``first, first + 1,
    ...`` of ``kernel`` and no row belongs to any other."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), n = rows.shape, kernel.shape[-2 if transposed else -1]
    tm = _row_tile(m, kernel.shape[0])
    # the kernel reads group g's weights at ``g - group_offset``
    return gmm(rows, kernel, sizes, preferred_element_type=rows.dtype,
               tiling=_gmm_tiles(tm, k, n, rows.dtype.itemsize),
               group_offset=None if first is None else -first,
               transpose_rhs=transposed)


def _gmm_fwd(rows, kernel, sizes, first, transposed):
    return _gmm(rows, kernel, sizes, first, transposed), (
        rows, kernel, sizes, first)


def _gmm_bwd(transposed, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    rows, kernel, sizes, first = res
    assert first is None, "a stack of layers is the serving tick's: no vjp"
    (m, k), n = rows.shape, kernel.shape[-2 if transposed else -1]
    tm = _row_tile(m, kernel.shape[0])
    d_rows = gmm(g, kernel, sizes, preferred_element_type=rows.dtype,
                 tiling=_gmm_tiles(tm, n, k, rows.dtype.itemsize),
                 transpose_rhs=not transposed)
    # the weights' gradient in the layout the weights lie in: [k, n] is
    # rows^T g, [n, k] is g^T rows
    lhs, rhs, (a, b) = (g, rows, (n, k)) if transposed else (rows, g, (k, n))
    ta = _tile(a, 1024)
    d_kernel = tgmm(lhs.swapaxes(0, 1), rhs, sizes,
                    preferred_element_type=kernel.dtype,
                    tiling=(tm, ta, _tile(b, max(128, _GMM_GRAD_TILE // ta
                                                 // 128 * 128))),
                    num_actual_groups=kernel.shape[0])
    return d_rows, d_kernel, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows: jax.Array, kernel: jax.Array, counts: jax.Array,
                   layer: Optional[jax.Array] = None,
                   half: Optional[int] = None,
                   transposed: bool = False) -> jax.Array:
    """``rows[start_e : start_e + counts[e]] @ W[e]`` for every expert
    ``e``: ``rows`` [m, k] sorted by expert, ``counts`` [E] rows an expert
    (rows behind the last expert's belong to none and come back undefined).
    ``kernel`` holds the ``W[e]`` [k, n] on its last two axes and, before
    them, the expert axis, with a layer axis in front where ``layer`` says
    which layer is meant (the serving tick's whole stack) and a GLU chunk
    axis behind where ``half`` says which half: ``[(L,) E, (2,) k, n]``;
    ``transposed``: the ``W[e]`` lie as ``[n, k]`` (a non-gated expert's
    fc1, the module's schema).

    On a TPU target this is jax's grouped-matmul Pallas kernel (megablox
    ``gmm``; ``tgmm`` for the weights' gradient), which visits (row tile,
    group) pairs and streams each touched group's weights once a row tile,
    in tiles as large as fast memory takes (the whole k, then as much of n
    as 3 MB holds): at the serving tick's few rows an expert the layer is
    weight-streaming, and small tiles pay a grid step per tile (PERF.md,
    PR 31: 4.1 ms a layer against ``jax.lax.ragged_dot``'s 8.7 at 256
    experts x 8 rows); at the trainer's hundreds of rows an expert a row
    tile is 512, so that the weights are streamed a quarter as often.  The
    kernel is handed the WHOLE leaf as ``[groups, k, n]``, a view (a slice
    of the leaf would be a copy of it), with the sizes of the meant layer's
    groups and the place in the leaf where they start.  A width need not
    be on the 128-lane grid (the kernel cuts its last tile: :func:`_tile`;
    1,856 runs so on the chip, PR 53) but on a 64 grid and at least 128.
    Elsewhere (the CPU tests, tiny widths), ``jax.lax.ragged_dot`` on the
    slice: the same sums."""
    from megatron_llm_tpu.core.parallel_state import target_platform

    m, (k, n) = rows.shape[0], kernel.shape[-2:][::-1 if transposed else 1]
    within = (slice(None),) + (() if half is None else (half,))
    pick = (() if layer is None else (layer,)) + within
    if target_platform() != "tpu" or k % 64 or n % 64 or min(k, n) < 128:
        sliced = kernel[pick]
        return jax.lax.ragged_dot(
            rows, sliced.swapaxes(-1, -2) if transposed else sliced, counts)

    # the kernel lays out its tiles in plain jnp from the groups' sizes (a
    # cumsum, two searches: a loop of tiny operations before every call),
    # so it is told of ONE layer's groups and where in the stack they start,
    # not of every layer's with none but one's non-empty (608 groups for 16
    # a call, three calls a layer: 6 ms a tick of the LFM2 cell, PR 58)
    groups = kernel.shape[:-2] if layer is None else kernel.shape[1:-2]
    sizes = jnp.zeros(groups, counts.dtype).at[within].set(counts)
    first = None if layer is None else jnp.asarray(layer * sizes.size,
                                                   jnp.int32)
    pad = -m % _GMM_ROWS
    if pad:   # whole row tiles; the padding rows belong to no group
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = _gmm(rows, kernel.reshape(-1, *kernel.shape[-2:]).astype(rows.dtype),
               sizes.reshape(-1), first, transposed)
    return out[:m] if pad else out


def _grouped_linear(p_lin: Params, rows: jax.Array, counts: jax.Array,
                    row_expert: jax.Array, dt,
                    layer: Optional[jax.Array] = None,
                    half: Optional[int] = None,
                    transposed: bool = False) -> jax.Array:
    """``rows`` [R, k] sorted by expert through that expert's kernel: one
    grouped GEMM over the sorted rows, then the expert's int8 channel scale
    and bias where the leaf has them.  ``layer`` / ``half`` /
    ``transposed`` as :func:`grouped_matmul`."""
    kernel, scale = _expert_kernel(p_lin, dt)
    y = grouped_matmul(rows, kernel, counts, layer, half, transposed)

    def per_row(leaf):          # [(L,) E, (2,) n] -> [R, n]
        leaf = leaf if layer is None else leaf[layer]
        leaf = leaf if half is None else leaf[:, half]
        return leaf.astype(dt)[row_expert]

    if scale is not None:   # int8 per-channel scale
        y = y * per_row(scale)
    if "bias" in p_lin:
        y = y + per_row(p_lin["bias"])
    return y


def held_rows(cfg, assignments: int) -> int:
    """Rows of the dispatch's buffer for ``assignments`` (tokens x topk)
    router choices: all of them where every expert is held; for a share,
    ``moe_capacity_factor`` x the held experts' expected part, in whole
    row tiles of the grouped kernel, and never more than all."""
    m = cfg.model
    if m.experts_held == m.num_experts:
        return assignments
    rows = math.ceil(assignments * m.experts_held * m.moe_capacity_factor
                     / m.num_experts)
    return min(assignments, -(-rows // _GMM_ROWS_MANY) * _GMM_ROWS_MANY)


# The dispatch and the combine as a pair of transposes, each a GATHER in
# both directions (a scatter-add of tens of thousands of rows is the one
# thing the TPU does a row at a time).  ``order`` [R]: the flat assignment
# (token * K + slot) behind buffer row r; ``live`` [R]: the row holds an
# assignment; ``inv`` [T, K]: the buffer row of an assignment; ``valid``
# [T, K]: the assignment has one.  Where every expert is held every row is
# live and every assignment has one: both masks are None and cost nothing.


def _keep(mask, x):
    """``x`` where ``mask`` (broadcast over the trailing axis), else 0."""
    if mask is None:
        return x
    return jnp.where(mask if mask.ndim == x.ndim else mask[..., None], x, 0)


@jax.custom_vjp
def _dispatch(x, order, live, inv, valid):
    """rows[r] = x[token of row r], zero where the row is not live."""
    k_ = inv.shape[1]
    return _keep(live, x[order // k_])


def _dispatch_fwd(x, order, live, inv, valid):
    return _dispatch(x, order, live, inv, valid), (inv, valid)


def _dispatch_bwd(res, g):
    inv, valid = res
    back = _keep(valid, g[inv])                               # [T, K, h]
    dx = back.astype(jnp.float32).sum(1).astype(g.dtype)
    return dx, None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w, order, live, inv, valid):
    """y[t] = sum_k w[t, k] * out[row of (t, k)] over the assignments that
    have a row, accumulated in float32."""
    back = _keep(valid, out[inv])                             # [T, K, h]
    return jnp.einsum("tkh,tk->th", back.astype(jnp.float32),
                      w).astype(out.dtype)


def _combine_fwd(out, w, order, live, inv, valid):
    return (_combine(out, w, order, live, inv, valid),
            (out, w, order, live, inv, valid))


def _combine_bwd(res, dy):
    out, w, order, live, inv, valid = res
    k_ = inv.shape[1]
    g = _keep(live, dy[order // k_]).astype(jnp.float32)
    w_row = _keep(live, w.reshape(-1)[order])                 # [R]
    d_out = (g * w_row[:, None]).astype(out.dtype)
    # a row's weight gradient, made where the row lies and carried back
    dw_row = _keep(live, (g * out.astype(jnp.float32)).sum(-1))
    dw = _keep(valid, dw_row[inv]).astype(w.dtype)
    return d_out, dw, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_experts(cfg, experts, x: jax.Array, idx: jax.Array,
                     w: jax.Array, counts: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """sum_k w[t, k] * E_{idx[t, k]}(x[t]) over the HELD experts, for ``x``
    [T, h], with no capacity where all are held: sort the T*K assignments
    by expert, run the sorted rows through one grouped GEMM for each half
    of fc1 and one for fc2, and gather each token's results back (a gather
    through the inverse permutation, not a scatter-add: deterministic, and
    no scatter on the TPU, forward or backward).  A share
    (``moe_experts_held``): an absent expert's assignments sort behind
    every held one and get no row; the buffer is :func:`held_rows` long.
    ``experts`` is a layer's subtree or :class:`StackedExperts`; ``counts``
    [num_experts] the rows the router gave each expert.

    Returns (out [T, h], assignments that ran, held assignments dropped
    for want of a row: zero where all experts are held)."""
    m = cfg.model
    t_, k_ = idx.shape
    dt = x.dtype
    held, first = m.experts_held, m.moe_first_held_expert
    n_rows = held_rows(cfg, t_ * k_)
    layer = None
    if isinstance(experts, StackedExperts):
        experts, layer = experts
    with jax.named_scope("dispatch"):
        flat = idx.reshape(t_ * k_)
        share = held < m.num_experts
        if share:   # an absent expert's assignments sort behind the held
            flat = flat - first
            flat = jnp.where((flat >= 0) & (flat < held), flat, held)
        order = jnp.argsort(flat)                 # stable: rows by expert
        inv = jnp.argsort(order).reshape(t_, k_)  # an assignment's row
        counts = counts.astype(jnp.int32)
        ran, dropped = counts.sum().astype(jnp.float32), jnp.float32(0)
        live = valid = None
        row_expert = flat[order[:n_rows]]
        if share:
            order = order[:n_rows]
            live = row_expert < held
            valid = (flat.reshape(t_, k_) < held) & (inv < n_rows)
            inv = jnp.minimum(inv, n_rows - 1)
            row_expert = jnp.minimum(row_expert, held - 1)
            # rows an expert runs: what the router gave it, as far as the
            # buffer reaches
            given = counts[first:first + held]
            start = jnp.cumsum(given) - given
            counts = jnp.clip(n_rows - start, 0, given)
            ran = counts.sum().astype(jnp.float32)
            dropped = given.sum().astype(jnp.float32) - ran
        rows = _dispatch(x, order, live, inv, valid)          # [R, h]
    with jax.named_scope("expert_gemm"):
        def linear(name, rows, half=None, transposed=False):
            return _grouped_linear(experts[name], rows, counts, row_expert,
                                   dt, layer, half, transposed)

        if m.glu_activation is not None:
            inter = glu_product(m.glu_activation, linear("fc1", rows, 0),
                                linear("fc1", rows, 1), m.swiglu_limit)
        else:
            inter = get_mlp_activation(None, m.activation)(
                linear("fc1", rows, transposed=True))
        out = linear("fc2", inter)                            # [R, h]
    with jax.named_scope("combine"):
        return _combine(out, w, order, live, inv, valid), ran, dropped


def use_dropless(cfg) -> bool:
    """Token-choice routing on one expert shard takes the dropless
    dispatch.  What keeps the capacity einsums: ``ep > 1`` (their
    sharding constraints are what makes XLA emit the data<->expert
    all-to-all; a grouped GEMM over an expert-sharded stack has no such
    rule yet) and expert-choice routing (capacity is its definition)."""
    return (cfg.model.moe_router_type == "topk"
            and cfg.parallel.expert_parallel_size == 1)


def route_expert_choice(
    cfg, router_logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-choice routing (Zhou et al. 2022): each expert selects its
    top-C tokens by router affinity — perfectly balanced by construction,
    so no load-balance aux loss is needed (only the optional z-loss).

    Note: within a routing group, experts compare tokens across positions,
    which leaks future-token information into the selection — fine for
    encoders/bidirectional models and for research runs; causal-LM training
    should prefer the default top-k token-choice routing.

    Returns (combine [G,T,E,C], dispatch bool, aux[2]) like route_tokens.
    """
    g_, t_, e_ = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)  # token-over-experts affinity
    # experts pick tokens: top-C over the T axis of [G, E, T]
    vals, idx = jax.lax.top_k(probs.transpose(0, 2, 1), capacity)  # [G,E,C]
    sel = jax.nn.one_hot(idx, t_, dtype=jnp.float32)  # [G,E,C,T]
    combine = (sel * vals[..., None]).transpose(0, 3, 1, 2)  # [G,T,E,C]
    dispatch = combine > 0.0
    # The Switch balance loss is identically at its optimum under EC (every
    # expert serves exactly C tokens), so reporting it would be a constant.
    # The balance slot instead carries EC's real health signal: the
    # DROPPED-TOKEN fraction (tokens selected by no expert). 0.0 = full
    # coverage. Metric-only: aux_loss_coeffs zeroes the balance coefficient
    # for expert_choice, so this never enters the training loss.
    covered = dispatch.any(axis=(2, 3))  # [G, T]
    dropped = 1.0 - covered.mean().astype(jnp.float32)
    aux = _aux(dropped, _router_z_loss(router_logits),
               dispatch.sum((0, 1, 3)).astype(jnp.float32))
    return combine, dispatch, aux


def route_tokens(
    cfg, router_logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-expert capacity.

    ``router_logits``: [G, T, E] fp32. Returns:
      combine  [G, T, E, C] fp32 — gate weight of token t in expert e slot c
      dispatch [G, T, E, C] bool — combine != 0
      aux      [2] fp32 — (load-balance loss, router z-loss), unweighted
    """
    m = cfg.model
    g_, t_, e_ = router_logits.shape
    k_ = m.moe_router_topk

    probs = jax.nn.softmax(router_logits, axis=-1)  # fp32
    gate, idx = jax.lax.top_k(probs, k_)  # [G, T, K]
    if m.moe_normalize_gates:
        # Mixtral convention: renormalize the selected gates to sum to 1
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    mask = jax.nn.one_hot(idx, e_, dtype=jnp.float32)  # [G, T, K, E]

    # Position of each (token, slot) in its expert's buffer. Priority order is
    # (slot, token): all first choices are seated before any second choice —
    # the GShard convention, so capacity pressure drops k=2 traffic first.
    mk = mask.transpose(0, 2, 1, 3).reshape(g_, k_ * t_, e_)
    pos = (jnp.cumsum(mk, axis=1) - mk).reshape(g_, k_, t_, e_).transpose(0, 2, 1, 3)
    pos_tk = (pos * mask).sum(-1).astype(jnp.int32)  # [G,T,K] pos in expert
    fits = pos_tk < capacity

    # load-balance aux loss (Switch eq. 4, generalized to top-k): fraction of
    # tokens dispatched to e (all slots, /k so it sums to 1) x mean router
    # prob for e, scaled by E — equals 1.0 under perfectly uniform routing.
    frac_tokens = mask.sum(2).mean((0, 1)) / k_    # [E]
    frac_probs = probs.mean((0, 1))                # [E]
    balance = e_ * jnp.sum(frac_tokens * frac_probs)
    aux = _aux(balance, _router_z_loss(router_logits), mask.sum((0, 1, 2)))

    gate_kept = gate * fits.astype(gate.dtype)                  # [G, T, K]
    slot = jax.nn.one_hot(pos_tk, capacity, dtype=jnp.float32)  # [G, T, K, C]
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gate_kept, mask, slot)
    dispatch = combine > 0.0
    return combine, dispatch, aux


def moe_sublayer(cfg, p: Params, x: jax.Array,
                 router_x: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over [b, s, h]; tokens route in per-sequence-chunk groups of
    ``moe_group_size``. Returns (out, aux[AUX_LEN]).  ``router_x``
    [b, s, h] is what the router reads where that is not ``x``
    (``moe_router_input``: the dropless dispatch only).

    Replaces mlp_sublayer (transformer.py) on MoE layers; the dense path's
    GLU chunk-2 convention (glu_activations.py:14-16) is preserved per expert.
    """
    m = cfg.model
    b, s, h = x.shape
    if use_dropless(cfg):
        with jax.named_scope("moe"):
            xt = x.reshape(b * s, h)
            idx, w, counts, aux = route(
                cfg, p["router"],
                xt if router_x is None else router_x.reshape(b * s, h))
            out, ran, dropped = dropless_experts(
                cfg, p["experts"], xt, idx, w, counts)
            if m.experts_held < m.num_experts:
                first = m.moe_first_held_expert
                aux = aux.at[4].set(ran).at[5].set(dropped).at[6].set(
                    (counts[first:first + m.experts_held] > 0).sum()
                    .astype(jnp.float32))
            if "shared" in p:
                from megatron_llm_tpu.models.transformer import mlp_sublayer

                with jax.named_scope("shared_expert"):
                    # the S shared experts are one MLP of S x the width:
                    # its output is their sum
                    shared = mlp_sublayer(cfg, p["shared"], xt)
                    if m.moe_shared_combination == "average":
                        shared = shared * (1.0 / m.moe_shared_experts)
                    out = out + shared
            return out.reshape(b, s, h), aux
    assert "shared" not in p and "bias" not in p["router"] and (
        m.moe_score_func == "softmax" and router_x is None
        and m.moe_routed_scaling_factor == 1.0), (
        "the capacity dispatch (ep > 1, expert_choice) knows the softmax "
        "top-k router only: no shared expert, selection bias, sigmoid "
        "scores, scaling factor or router before the attention")
    # GShard grouping: route fixed-size chunks of the sequence independently
    # so dispatch/combine stay O(group * capacity), not O(seq^2) — at 32K seq
    # an ungrouped [s, E, C~s] one-hot would be gigabytes per sample.
    gsz = min(s, m.moe_group_size)
    assert s % gsz == 0, (
        f"seq_length {s} not a multiple of moe_group_size {gsz}"
    )
    x = x.reshape(b * (s // gsz), gsz, h)

    w_router = p["router"]["kernel"]  # fp32
    router_logits = x.astype(jnp.float32) @ w_router  # [G, T, E]
    if m.moe_router_type == "expert_choice":
        combine, dispatch, aux = route_expert_choice(
            cfg, router_logits, moe_capacity_expert_choice(cfg, gsz)
        )
    elif m.moe_router_type == "topk":
        combine, dispatch, aux = route_tokens(
            cfg, router_logits, moe_capacity(cfg, gsz)
        )
    else:  # loud failure for configs that bypassed finalize validation
        raise ValueError(f"unknown moe_router_type {m.moe_router_type!r}")

    dt = x.dtype
    xe = jnp.einsum("gtec,gth->gech", dispatch.astype(dt), x)  # [b, E, C, h]
    xe = _ep_constraint(xe, 1)

    experts = p["experts"]
    fc1, s1 = _expert_kernel(experts["fc1"], dt)
    glu = m.glu_activation is not None
    # [g,e,c,(2,)f]; the bias broadcast [1,e,1,(2,)f] covers both layouts
    y = jnp.einsum("gech,euhf->gecuf" if glu else "gech,efh->gecf", xe, fc1)
    if s1 is not None:  # int8 per-channel scale (same broadcast as bias)
        y = y * s1.astype(dt)[None, :, None]
    if "bias" in experts["fc1"]:
        y = y + experts["fc1"]["bias"].astype(dt)[None, :, None]
    if glu:
        inter = glu_product(m.glu_activation, y[..., 0, :], y[..., 1, :],
                            m.swiglu_limit)
    else:
        inter = get_mlp_activation(None, m.activation)(y)
    fc2, s2 = _expert_kernel(experts["fc2"], dt)
    out_e = jnp.einsum("gecf,efh->gech", inter, fc2)
    if s2 is not None:
        out_e = out_e * s2.astype(dt)[None, :, None]
    if "bias" in experts["fc2"]:
        out_e = out_e + experts["fc2"]["bias"].astype(dt)[None, :, None]
    out_e = _ep_constraint(out_e, 1)

    out = jnp.einsum("gech,gtec->gth", out_e, combine.astype(dt))
    return out.reshape(b, s, h), aux


def zero_aux() -> jax.Array:
    """Aux placeholder for dense layers (keeps scan carries uniform)."""
    return jnp.zeros((AUX_LEN,), jnp.float32)


def aux_loss_coeffs(cfg) -> Tuple[float, float]:
    """(balance_coeff, z_coeff) to apply to the summed aux pair.

    Expert-choice routing is balanced by construction, so it has no
    balance LOSS; its aux[0] slot instead reports the dropped-token
    fraction (route_expert_choice) as a metric. That value is
    piecewise-constant in the router weights (gradient-free) and must NOT
    enter the trained loss — the balance coefficient stays zeroed for EC
    regardless of what the slot reports.
    """
    m = cfg.model
    balance = 0.0 if m.moe_router_type == "expert_choice" else m.moe_aux_loss_coeff
    return balance, m.moe_z_loss_coeff


def _group_limited(m, pick: jax.Array) -> jax.Array:
    """``pick`` [T, E] with the experts outside a token's ``moe_topk_group``
    best groups at ``-inf`` (DeepSeek-V3's ``n_group`` / ``topk_group``;
    ``pick`` itself where there is one group): the experts stand in
    ``moe_n_group`` equal runs, a group's score is the sum of its two
    largest selection scores, and of equal groups the earlier stays.  At
    the file's end, so that the lines above stand where they stood (the
    grouped kernel's payload names its callers' lines)."""
    groups, stay = m.moe_n_group, m.moe_topk_group
    if groups <= 1:
        return pick
    t, e = pick.shape
    score = jax.lax.top_k(pick.reshape(t, groups, e // groups), 2)[0].sum(-1)
    _, best = jax.lax.top_k(score, stay)
    alive = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(1)
    return jnp.where(jnp.repeat(alive, e // groups, axis=1), pick, -jnp.inf)
