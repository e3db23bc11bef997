"""The serving engine's per-sequence memory: the page pool, the state pool
and the prefix trie, below the scheduler (generation/engine.py).

* :class:`PagedKVPool` — the device page pool and its host-side
  refcounting allocator; one a page class (a patterned model has a full and
  a window class, models/transformer.py ``pool_classes``).
* :class:`StatePool` — the same allocator over state slots, for the layers
  that keep a constant-size recurrent state a sequence (power retention;
  a hybrid's gated-delta or Mamba-2 layers, BESIDE its attention layers'
  page pool of latent rows or K/V pages).
* :class:`PrefixCache` — the host-side radix trie over page-aligned token
  chunks, and the eviction order of its idle pages.
* :class:`ClassMemory` — what the engine holds of ONE class: the pool, the
  host mirror of the tick's table, the commitment ledger, and admit, grant,
  slide and release of what one sequence holds of it (:class:`SeqMemory`).
* :func:`refuse_unserved` — what each kind of per-sequence memory does not
  carry yet, as ONE table (:data:`NOT_CARRIED`), said at start-up in a
  sentence.

A class owns HOW a sequence's memory is granted, slid and released, and
its ledger; the engine owns WHEN (admission, preemption, the order of
prefill, which row fails when a ledger is violated) and the lock: every
method here is called with the engine's ``_lock`` held and takes none.  A
further kind of memory is a pool class here and its rows of
:data:`NOT_CARRIED`.  This module imports nothing from
``generation/engine.py``, ``generation/server.py`` or
``generation/scheduling/`` (tests/test_pools_seam.py).
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu.core.parallel_state import PP_AXIS, TP_AXIS
from megatron_llm_tpu.models.language_model import _compute_dtype
from megatron_llm_tpu.models.transformer import pool_classes
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.observability import trace as obs_trace
from megatron_llm_tpu.ops import kv_quant

NULL_PAGE = 0


# ---- what is not carried yet: one table ------------------------------------
#
# A row is (kind of per-sequence memory, feature) -> why the feature does
# not carry that kind yet.  The kinds are what ``cfg``'s pool classes show
# (:func:`memory_kind`): ``classes`` (a full and a window page class),
# ``latent`` (MLA's one-leaf pool), ``indexed`` (latent rows AND index
# keys, two leaves under one page id: learned sparse attention), ``state``
# (power retention's state
# slots), ``hybrid`` (a state class BESIDE a page class: a sequence holds a
# slot and pages), ``tails`` (the same beside a state class of conv tails
# alone: gated short convolutions, no recurrent state), ``blocks`` (one
# class of K/V pages under a block-causal mask, generated from by diffusion
# over blocks: generation/blocks.py), ``loop`` (one class of K/V pages whose
# layer axis is a slot a layer and PASS of a looped stack: ``loop_steps``);
# ``paged``, one class of K/V pages, carries every
# feature and has no row.  The features are what a caller of :func:`refuse_unserved` may
# ask for: ``kv_dtype`` other than bf16, a ``tp`` or ``pp`` mesh, a
# ``draft`` model (--spec_k), the cross-replica ``handoff``, a request's
# ``log_probs``.  ``pattern`` is no feature but the kind's own
# precondition (a stack the pool has no class for), and ``share`` one of
# any kind (a held share of the experts under a mesh).  A new kind of
# memory adds its rows here, and tests/test_pools_seam.py asks for a case
# a row.

FEATURES = ("kv_dtype", "tp", "pp", "draft", "handoff", "log_probs")

# the start of a kind's sentence: what it keeps a sequence
KEEPS = {
    "classes": ("a layer pattern (sliding_window_layout {layout}) keeps its "
                "window layers' and its full layers' keys in two page "
                "classes"),
    "latent": ("latent attention (attention_type 'mla') keeps ONE latent "
               "row a token, key and value at once"),
    "indexed": ("learned sparse attention (index_topk {topk}) keeps a "
                "latent row AND an index key a token, two leaves under one "
                "page id"),
    "state": ("power retention (attention_type 'retention') keeps a "
              "constant-size recurrent state a sequence"),
    "hybrid": ("a hybrid stack ({stack}) keeps a recurrent state a sequence "
               "for its linear layers beside pages of {rows} for its "
               "attention layers"),
    "blocks": ("generation by diffusion over blocks (diffusion_block_length "
               "{block}) keeps its K/V pages under a block-causal mask and "
               "a block's ids and known flags a slot on the device"),
    "loop": ("a looped stack (loop_steps {loops}) runs its layers {loops} "
             "times over the same weights and keeps every pass's keys and "
             "values in page slots of its own"),
    "tails": ("a stack of gated short convolutions ({stack}) keeps the "
              "conv's last inputs a sequence (a tail a layer, no recurrent "
              "state) beside pages of keys and values for its attention "
              "layers"),
}

_CLASSES_MESH = (
    "tensor- or pipeline-parallel serving (tp {tp}, pp {pp}): the pool's "
    "shardings and the stage pipeline name one leaf")

NOT_CARRIED = {
    ("share", "mesh"): (
        "moe_experts_held {held} of {experts} is one chip's share of an "
        "expert-parallel layer, its attention data-parallel: serve it on "
        "one chip, not on a tp {tp} x pp {pp} mesh (the other chips' "
        "experts and the exchange with them are not here)"),
    ("classes", "pattern"): (
        "this pattern (more than one window size, no full layer, latent "
        "attention or a dense prefix): the pool knows a full class and ONE "
        "window class of K/V rows over one stack"),
    ("classes", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: a page's scales are set by the page's "
        "first write, and no test holds them through a window class's "
        "release and re-grant"),
    ("classes", "tp"): _CLASSES_MESH,
    ("classes", "pp"): _CLASSES_MESH,
    ("classes", "draft"): (
        "--spec_k: the verify tick and the draft cache are built for one "
        "block table a sequence"),
    ("classes", "handoff"): (
        "the cross-replica KV handoff: its wire format names one page list "
        "a sequence"),
    ("classes", "log_probs"): (
        "return_log_probs (prompt scoring): the scoring chunk walks one "
        "block table a sequence"),
    ("latent", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: page scales are kept per KV head, and a "
        "latent row has none"),
    ("latent", "tp"): (
        "tensor-parallel serving (tp {tp}): the pool shards over KV heads, "
        "and a latent row has none"),
    ("latent", "pp"): (
        "pipeline-parallel serving (pp {pp}): its stages split one scanned "
        "stack, and this family's dense prefix layers come before it"),
    ("latent", "draft"): (
        "--spec_k: the verify tick and the draft cache are built for a K/V "
        "pool"),
    ("latent", "handoff"): (
        "the cross-replica KV handoff: its wire format names a K and a V "
        "leaf"),
    ("indexed", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: the indexer's scores are made from the "
        "index keys as they were written, a page's scales are kept per KV "
        "head, and neither leaf has one"),
    ("indexed", "tp"): (
        "tensor-parallel serving (tp {tp}): the pool shards over KV heads, "
        "and neither a latent row nor an index key has one (the indexer's "
        "own heads are not sharded either)"),
    ("indexed", "pp"): (
        "pipeline-parallel serving (pp {pp}): the stage pipeline hands ONE "
        "paged leaf from stage to stage, and this pool keeps two"),
    ("indexed", "draft"): (
        "--spec_k: the verify tick and the draft cache are built for a K/V "
        "pool, and a draft model would need an indexer of its own"),
    ("indexed", "handoff"): (
        "the cross-replica KV handoff: its wire format names a K and a V "
        "leaf, and an index key is neither"),
    ("state", "pattern"): (
        "a stack that mixes it with a page class (a window pattern, a dense "
        "prefix, latent attention): power retention's state is served "
        "alone; the state class BESIDE a page class is a hybrid's "
        "(linear_layout's gated-delta layers, sublayer_pattern's Mamba-2 "
        "ones)"),
    ("state", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: the state is a float32 sum that is decayed "
        "and added to at every token, and storing it lower is a different "
        "result"),
    ("state", "tp"): (
        "tensor-parallel serving (tp {tp}): the state pool is not sharded "
        "over its KV heads"),
    ("state", "pp"): (
        "pipeline-parallel serving (pp {pp}): the stage pipeline hands a "
        "paged leaf from stage to stage"),
    ("state", "draft"): (
        "--spec_k: a rejected draft token would have to roll the state "
        "back, and nothing keeps the state before it"),
    ("state", "handoff"): (
        "the cross-replica KV handoff: its wire format names pages of keys "
        "and values"),
    ("state", "log_probs"): (
        "return_log_probs (prompt scoring): the scoring chunk feeds many "
        "tokens a row through a block table"),
    ("hybrid", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: the state is a float32 sum that every "
        "token changes, storing it lower is a different result, and no "
        "test holds a page's scales beside a state slot"),
    ("hybrid", "tp"): (
        "tensor-parallel serving (tp {tp}): the state pool is not sharded "
        "over its heads, and the pool's shardings name one leaf"),
    ("hybrid", "pp"): (
        "pipeline-parallel serving (pp {pp}): the stage pipeline hands ONE "
        "paged leaf from stage to stage, and this stack keeps two"),
    ("hybrid", "draft"): (
        "--spec_k: a rejected draft token would have to roll the state "
        "back, and nothing keeps the state before it"),
    ("hybrid", "handoff"): (
        "the cross-replica KV handoff: its wire format names pages of keys "
        "and values, and a state slot is neither"),
    ("hybrid", "log_probs"): (
        "return_log_probs (prompt scoring): the scoring chunk feeds many "
        "tokens a row through one block table, and a linear layer takes "
        "one row a token from its state slot, beside latent rows and K/V "
        "pages alike"),
    ("blocks", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: a page's scales are set by the page's "
        "first write, which here is a denoise row's K/V of mask inputs "
        "that the commit rows then write over; no test holds them"),
    ("blocks", "tp"): (
        "tensor-parallel serving (tp {tp}): the block tick works out its "
        "rows' shared page walks for the whole pool row, not a shard of "
        "the heads, and no test holds the block state on a mesh"),
    ("blocks", "pp"): (
        "pipeline-parallel serving (pp {pp}): the stage pipeline carries "
        "the causal tick's one mask position a row"),
    ("blocks", "draft"): (
        "--spec_k: a draft model proposes the NEXT tokens of a causal "
        "sequence, and a block's positions are unmasked in no fixed order"),
    ("blocks", "handoff"): (
        "the cross-replica KV handoff: it ships a prompt's pages up to its "
        "last token, and a block model's K/V ends on a block boundary"),
    ("blocks", "log_probs"): (
        "return_log_probs (prompt scoring): the scoring chunk scores "
        "token i + 1 from position i under a causal mask, and this model "
        "has no such distribution"),
    ("loop", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: a page's scales are set by the page's "
        "first write, a slot a pass, and no test holds {loops} passes' "
        "scales against the reference"),
    ("loop", "tp"): (
        "tensor-parallel serving (tp {tp}): no sharding rule names the "
        "exit gate, and no test holds the pool's {loops} x depth slots on "
        "a shard of the heads"),
    ("loop", "pp"): (
        "pipeline-parallel serving (pp {pp}): a stage owns a slice of the "
        "layers ONCE, and a looped stack's rows come back to the first "
        "stage {loops} times"),
    ("loop", "draft"): (
        "--spec_k: the verify tick's rejected rows would have to be "
        "rolled back in every pass's slots, and no test holds that"),
    ("loop", "handoff"): (
        "the cross-replica KV handoff: its wire format names a page's "
        "rows by the model's depth, not by {loops} x depth slots"),
    ("tails", "kv_dtype"): (
        "--kv_dtype {kv_dtype}: no test holds a page's scales beside a "
        "tail slot, and the tail is the conv's inputs as they were fed"),
    ("tails", "tp"): (
        "tensor-parallel serving (tp {tp}): the tails are not sharded over "
        "their channels, and the pool's shardings name one leaf"),
    ("tails", "pp"): (
        "pipeline-parallel serving (pp {pp}): the stage pipeline hands ONE "
        "paged leaf from stage to stage, and this stack keeps two"),
    ("tails", "draft"): (
        "--spec_k: a rejected draft token would have to roll the tail "
        "back, and nothing keeps the tail before it"),
    ("tails", "handoff"): (
        "the cross-replica KV handoff: its wire format names pages of keys "
        "and values, and a tail slot is neither"),
    ("tails", "log_probs"): (
        "return_log_probs (prompt scoring): the scoring chunk feeds many "
        "tokens a row through one block table, and a conv layer takes one "
        "row a token from its tail slot"),
}


def memory_kind(cfg) -> str:
    """The kind of per-sequence memory ``cfg``'s model is served from, read
    off its pool classes: every class a state, a state class beside a page
    class, more than one page class, or one (of latent rows or K/V)."""
    if cfg.model.retention:
        return "state"          # whatever else the stack claims to be
    states = [cls.state for cls in pool_classes(cfg)]
    if all(states):
        return "state"
    if any(states):
        return "tails" if cfg.model.short_conv else "hybrid"
    if len(states) > 1:
        return "classes"
    if cfg.model.diffusion_block_length:
        return "blocks"
    if cfg.model.loop_steps > 1:
        return "loop"
    if cfg.model.mla and cfg.model.index_topk:
        return "indexed"
    return "latent" if cfg.model.mla else "paged"


def refuse_unserved(cfg, *, kv_dtype: str = "bf16", mesh=None,
                    draft: bool = False, handoff: bool = False,
                    log_probs: bool = False) -> None:
    """Raise a ``ValueError`` naming the first thing asked for that
    ``cfg``'s kind of memory does not carry yet (:data:`NOT_CARRIED`), at
    start-up (or at the request, for ``log_probs``), in a sentence, instead
    of failing inside a compile.  Callers pass what they know; what they
    leave out is not asked for.  Admission, chunked prefill, the prefix
    trie, copy-on-write and preemption carry every kind (the prefix cache
    of a model with a state class is off, not refused: a trie of pages
    does not hold the state at a page's boundary)."""
    m = cfg.model
    tp = mesh.shape.get(TP_AXIS, 1) if mesh is not None else 1
    pp = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
    kind = memory_kind(cfg)
    asked = dict(kv_dtype=kv_dtype != "bf16", tp=tp > 1, pp=pp > 1,
                 draft=draft, handoff=handoff, log_probs=log_probs)
    if m.num_experts is not None and m.experts_held < m.num_experts and (
            tp > 1 or pp > 1):
        kind, feature = "share", "mesh"
    elif kind == "classes" and (
            len(pool_classes(cfg)) > 2 or pool_classes(cfg)[0].window
            is not None or m.mla or m.dense_prefix_layers):
        feature = "pattern"
    elif kind == "state" and (
            m.sliding_window_layout or m.dense_prefix_layers or m.mla):
        feature = "pattern"
    else:
        feature = next((f for f in FEATURES
                        if asked[f] and (kind, f) in NOT_CARRIED), None)
    if feature is None:
        return
    why = NOT_CARRIED[kind, feature].format(
        kv_dtype=kv_dtype, tp=tp, pp=pp, held=m.moe_experts_held,
        experts=m.num_experts, loops=m.loop_steps)
    if kind == "share":
        raise ValueError(why)
    keeps = KEEPS[kind].format(
        layout=m.sliding_window_layout,
        stack=(f"sublayer_pattern {m.sublayer_pattern}"
               if m.sublayer_pattern else f"linear_layout {m.linear_layout}"),
        rows="latent rows" if m.mla else "keys and values",
        block=m.diffusion_block_length, loops=m.loop_steps,
        topk=m.index_topk)
    raise ValueError(
        f"{keeps}, which {why} "
        "does not carry yet. Serve this model on one chip with --kv_dtype "
        "bf16 and --spec_k 0.")


class PagedKVPool:
    """Device page pool + host refcounting allocator.

    The device array is ONE leaf ``[L, P, page, row]`` (``kv``) whose row
    ``ops/kv_quant.py`` owns and derives from ``(nkv, d, dtype)``: a head's
    key and value side by side, so a page is one copy for the kernel and a
    token one scatter for the write, in whole 128-lane rows (Falcon-7B's
    head of 64 included); a latent model's row is its padded latent.  The
    pool keeps that ONE layout from the write to the kernel: every tick
    program carries it through the layer scan and updates it in place
    (models/transformer.py ``LayerPool``).  Code off the tick's hot path
    reads the logical ``(page, offset, head, d)`` view through kv_quant
    (:meth:`logical_kv`, the handoff's export / import).  The allocator
    is host-side python — alloc/release happen at request
    admission/retirement and page-boundary crossings, far below tick
    frequency.

    Page states (disjoint, tests/test_prefix_cache.py invariants):

    * **free** — on the free list, refcount 0, not cached;
    * **referenced** — refcount > 0 (held by >= 1 request's block table),
      possibly ALSO registered in the prefix cache;
    * **cached-idle** — refcount 0 but registered in the prefix cache
      (``cached``): reusable by a future match, reclaimable by
      ``evict_hook`` (PrefixCache.evict, LRU leaf-first) when ``alloc``
      outruns the free list.

    How many pages are cached-idle is KEPT, not walked: the count changes
    only where a page crosses a boundary (``incref`` of a cached page
    0 -> 1, ``release`` of one 1 -> 0, ``set_cached`` as a page enters or
    leaves ``cached``), so ``num_evictable`` and ``num_available`` are
    reads.  Pages that ``release`` leaves cached-idle are handed to
    ``idle_hook`` (PrefixCache.note_idle), which keeps the eviction order.

    With ``draft_cfg`` (speculative decoding, generation/speculative/),
    the pool carries a SECOND leaf (``draft_kv``) shaped by the draft
    model — same ``num_pages``, same page ids.  A page id then addresses
    both models' K/V for the same token positions: one block table, one
    refcount, one commitment ledger and one prefix trie govern both
    caches, so admission/preemption accounting stays deadlock-proof with
    zero new allocator states.
    """

    def __init__(self, cfg, num_pages: int, page_size: int, dtype=None,
                 mesh: Optional[Mesh] = None, draft_cfg=None,
                 kv_dtype: str = "bf16", layers: Optional[int] = None,
                 page_class: Optional[str] = None,
                 state: Optional[bool] = None):
        m = cfg.model
        # one page class of a patterned model's pool (``page_class`` names
        # it in the counters' ``class=`` label; ``layers``: how many of the
        # model's layers keep their keys here).  None: the one pool of a
        # uniform model, every layer's, its counters unlabelled as ever
        self.page_class = page_class
        # (a looped stack: a slot a layer and pass, models/language_model.py)
        layers = m.cache_layer_slots if layers is None else layers
        dtype = dtype or _compute_dtype(cfg)
        assert kv_dtype in kv_quant.KV_DTYPES, (
            f"kv_dtype must be one of {kv_quant.KV_DTYPES}, got {kv_dtype!r}")
        # --kv_dtype (ISSUE 13): "bf16" keeps plain compute-dtype arrays —
        # byte-for-byte today's pool, every bitwise parity suite intact;
        # int8/fp8 store QuantPagedKV containers (values + per-page,
        # per-head scales, ops/kv_quant.py) for ~2x pages per chip.
        self.kv_dtype = kv_dtype
        self.compute_dtype = dtype
        # latent attention (MLA): a row of [normed latent | rotated rope
        # key] a token and layer, ONE storage head that the kernel reads as
        # key and as value, stored in whole 128-lane rows (576 -> 640
        # values; the lanes past ``latent_cache_width`` are zeros nobody
        # reads, 11% of the leaf).  Every other model: the K/V row
        self.latent = bool(m.mla)
        # :class:`StatePool`: a "page" is a sequence's whole state (power
        # retention's, or a hybrid's linear layers'), float32 whatever the
        # activations are
        self.state = bool(m.retention) if state is None else state
        refuse_unserved(cfg, kv_dtype=kv_dtype, mesh=mesh,
                        draft=draft_cfg is not None)
        # a state class whose sweep takes a run several rows a pass names
        # the host's count of its passes (slots, positions) -> int; None: a
        # sweep that walks, a pass a live row
        self.sweep_steps = None
        if self.state and m.short_conv:
            from megatron_llm_tpu.ops import gated_delta as gd_ops

            # tails alone, in the activations' dtype (what the conv is fed)
            self.head_dim = m.hidden_size
            kv = gd_ops.zero_tails((layers, num_pages), m.short_conv_kernel,
                                   self.head_dim, dtype)
        elif self.state and m.mamba:
            from megatron_llm_tpu.ops import mamba2 as mamba_ops

            self.sweep_steps = mamba_ops.sweep_steps
            self.head_dim = m.mamba_head_dim
            kv = mamba_ops.zero_state(
                (layers, num_pages), m.mamba_num_heads, self.head_dim,
                m.ssm_state_size, m.mamba_conv_kernel,
                m.mamba_conv_channels)
        elif self.state and m.delta:
            from megatron_llm_tpu.ops import gated_delta as gd_ops

            self.latent = False
            self.head_dim = m.linear_value_head_dim
            kv = gd_ops.zero_state(
                (layers, num_pages), m.linear_num_value_heads,
                m.linear_key_head_dim, self.head_dim,
                m.linear_conv_kernel_dim,
                2 * m.linear_num_key_heads * m.linear_key_head_dim
                + m.linear_num_value_heads * self.head_dim)
        elif self.state:
            from megatron_llm_tpu.ops import retention as ret_ops

            self.head_dim = m.kv_channels
            kv = ret_ops.zero_state((layers, num_pages),
                                    m.num_attention_heads_kv, self.head_dim)
        elif self.latent:
            # the logical view's head width: one head, the whole row
            self.head_dim = -(-m.latent_cache_width // 128) * 128
            kv = kv_quant.make_pool(
                (layers, num_pages, page_size, 1, self.head_dim), kv_dtype,
                dtype)
            if m.index_topk:
                # learned sparse attention: a token's index key in a leaf
                # of its own under the same page ids (a sweep over a
                # sequence's index keys then reads them alone).  One
                # allocation, one reference count, one trie entry a page
                kv = kv_quant.IndexedLatent(rows=kv, index=jnp.zeros(
                    (layers, num_pages, page_size, m.index_head_dim), dtype))
        else:
            self.head_dim = m.kv_channels
            kv = kv_quant.make_kv_pool(
                layers, num_pages, page_size, m.num_attention_heads_kv,
                self.head_dim, kv_dtype, dtype)

        # Tensor parallelism shards the pool's row over its KV heads (each
        # tp rank attends its own heads — the same decomposition as the qkv
        # column-parallel rule in parallel/tp.py; the row is head-major, so
        # a head's key|value pair stays on its shard). Block tables and the
        # allocator below stay host-side and apply to every shard alike;
        # tp=1 (or no mesh) degrades to a single-device replicated pool.
        # Quantized pools shard the scale leaf over the same heads
        # ([L, P, 2*nkv] -> tp), so a page's values and its scales
        # always live on the same shard.
        # Pipeline parallelism (ISSUE 20) additionally shards the pool
        # over the LAYER dim: each pp stage holds only its own L/pp
        # layers' pages — per-stage pool bytes are 1/pp of the tp-only
        # pool (the servable-model-size multiplier).  Page ids address
        # the same slot of every stage's slice, so block tables, the
        # trie, the allocator and the commitment ledger below stay
        # host-side and stage-agnostic, untouched.
        self.mesh = mesh
        tp = mesh.shape.get(TP_AXIS, 1) if mesh is not None else 1
        pp = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
        self.pp = pp
        if pp > 1:
            assert m.num_layers % pp == 0, (
                f"num_layers {m.num_layers} not divisible by pp {pp}")
        if tp > 1 or pp > 1:
            if tp > 1:
                assert m.num_attention_heads_kv % tp == 0, (
                    f"kv heads {m.num_attention_heads_kv} not divisible by "
                    f"tp {tp}")
            layer_ax = PP_AXIS if pp > 1 else None
            heads_ax = TP_AXIS if tp > 1 else None
            self.kv_sharding = NamedSharding(
                mesh, P(layer_ax, None, None, heads_ax))
            self._scale_sharding = NamedSharding(
                mesh, P(layer_ax, None, heads_ax))
            self.kv = self._place(kv)
        else:
            self.kv_sharding = (NamedSharding(mesh, P())
                                if mesh is not None else None)
            self._scale_sharding = self.kv_sharding
            self.kv = kv
        self.draft_cfg = draft_cfg
        self.draft_kv = None
        if draft_cfg is not None:
            dm = draft_cfg.model
            draft_kv = kv_quant.make_kv_pool(
                dm.num_layers, num_pages, page_size,
                dm.num_attention_heads_kv, dm.kv_channels, kv_dtype,
                _compute_dtype(draft_cfg))
            if pp > 1:
                assert dm.num_layers % pp == 0, (
                    f"draft num_layers {dm.num_layers} not divisible by "
                    f"pp {pp}")
            if tp > 1 or pp > 1:
                if tp > 1:
                    assert dm.num_attention_heads_kv % tp == 0, (
                        f"draft kv heads {dm.num_attention_heads_kv} not "
                        f"divisible by tp {tp}")
                draft_kv = self._place(draft_kv)
            self.draft_kv = draft_kv
        self.num_pages = num_pages
        self.page_size = page_size
        self.refcounts = np.zeros((num_pages,), np.int32)
        # pages owned by the prefix cache (trie nodes); PrefixCache adds
        # and removes them through ``set_cached``, which keeps the count
        # of those at refcount 0 beside the set
        self.cached: Set[int] = set()
        self._idle_cached = 0
        self.evict_hook = None  # PrefixCache.evict: (n) -> freed page list
        # PrefixCache.note_idle: (pages a release left cached-idle) -> None
        self.idle_hook = None
        # page 0 reserved as the null page (never allocated)
        self._free: deque = deque(range(1, num_pages))
        # a grant had to evict since the engine's step last looked: the
        # step that launches the next tick counts it dry and clears this
        self.reclaimed = False
        # the slow path counts and times itself (an ``alloc`` the free
        # list serves reads no clock): pages by where they came from, and
        # the calling thread's wall seconds picking victims.  Counting the
        # evictable pages is a read since the count is kept; its series
        # stays exported, at 0, for the readers that sum both
        reg = obs_registry.get_registry()
        cls = {} if page_class is None else {"class": page_class}
        self._m_alloc = {
            src: reg.counter(
                "mlt_engine_pool_alloc_pages_total",
                help="KV pool pages granted: free = off the free list, "
                     "evict = a cached-idle page the prefix cache had to "
                     "give up first (the pool had run dry); a patterned "
                     "model's series carry class= (full, window)",
                labels={**cls, "source": src}) for src in ("free", "evict")}
        # the three states a page is in (the null page in none), set by
        # the engine where a tick is applied
        self._m_pages = {
            state: reg.gauge(
                "mlt_engine_pool_pages",
                help="pool pages by state: referenced (a live sequence's "
                     "table names it), cached_idle (only the prefix cache "
                     "does), free; class = the page class (full: every "
                     "key kept, the one class of a uniform model; window: "
                     "a patterned model's window layers')",
                labels={"class": page_class or "full", "state": state})
            for state in ("referenced", "cached_idle", "free")}
        self._m_scan = {
            what: reg.counter(
                "mlt_engine_pool_scan_seconds_total",
                help="wall seconds the calling thread spent in the pool's "
                     "slow path: evict = the prefix cache picking and "
                     "unlinking victims; evictable = counting the cached "
                     "pages no request references, 0 since that count is "
                     "kept as references change and no longer walked",
                labels={"what": what}) for what in ("evictable", "evict")}

    def _place(self, pool):
        """device_put a pool (plain array or QuantPagedKV) under the tp
        sharding — a row over its heads, scales over their heads dim."""
        if kv_quant.is_quantized(pool):
            return jax.device_put(pool, kv_quant.QuantPagedKV(
                q=self.kv_sharding, scale=self._scale_sharding))
        return jax.device_put(pool, self.kv_sharding)

    @property
    def kv_statics(self) -> Tuple:
        """Compiled-program cache-key component for the KV storage mode
        (ISSUE 13): kv-quantization mode, storage dtype AND scale dtype —
        an int8 engine must never reuse a bf16 executable (and vice
        versa), and a future scale-dtype change re-keys too.  Replaces
        a pool-dtype key entry, which could not tell a container apart
        from its storage array."""
        if kv_quant.is_quantized(self.kv):
            return ("kv", self.kv_dtype, str(self.kv.q.dtype),
                    str(self.kv.scale.dtype))
        if isinstance(self.kv, kv_quant.IndexedLatent):
            return ("kv", "indexed", str(self.kv.rows.dtype),
                    self.kv.rows.shape[-1], self.kv.index.shape[-1])
        if self.latent:
            return ("kv", "latent", str(self.kv.dtype), self.kv.shape[-1])
        return ("kv", self.kv_dtype, str(self.kv.dtype))

    @property
    def draft_kv_statics(self) -> Tuple:
        if self.draft_kv is None:
            return ("draft_kv", None)
        if kv_quant.is_quantized(self.draft_kv):
            return ("draft_kv", self.kv_dtype, str(self.draft_kv.q.dtype),
                    str(self.draft_kv.scale.dtype))
        return ("draft_kv", self.kv_dtype, str(self.draft_kv.dtype))

    def _pools(self) -> List[Tuple[str, object, int]]:
        """(wire prefix, pool, head_dim) of every cache this pool holds."""
        pools = [("", self.kv, self.head_dim)]
        if self.draft_kv is not None:
            pools.append(("draft_", self.draft_kv,
                          self.draft_cfg.model.kv_channels))
        return pools

    def logical_kv(self, pages: Sequence[int], draft: bool = False):
        """Host copies of ``pages`` in the logical view, whatever the
        physical row: (keys, values), each ``[L, n, page, nkv, d]``
        (dequantized where the pool is quantized; a latent pool's row is
        both).  For tests, tools and debugging — not the tick."""
        _, pool, d = self._pools()[int(draft)]
        ids = np.asarray(list(pages), np.int32)
        got = jax.tree.map(lambda a: a[:, ids], kv_quant.values_of(pool)
                           if self.latent else pool)
        heads = np.asarray(
            kv_quant.dequantize_pages(got, jnp.float32)
            if kv_quant.is_quantized(got) else kv_quant.heads_view(got, d))
        return (heads, heads) if self.latent else kv_quant.split_kv(heads)

    def kv_pool_bytes(self) -> int:
        """Device bytes of the KV value storage, target + draft caches —
        the fixed budget the capacity bench holds constant while the
        kv_dtype varies (published as ``mlt_engine_kv_pool_bytes``)."""
        n = kv_quant.pool_nbytes(self.kv)
        if self.draft_kv is not None:
            n += kv_quant.pool_nbytes(self.draft_kv)
        return n

    def kv_stage_bytes(self) -> int:
        """Per-stage device bytes of the KV value storage: the layer dim
        is sharded over pp, so each stage holds ``kv_pool_bytes / pp`` —
        the number a pp=N replica's HBM budget actually pays (published
        as ``mlt_engine_kv_stage_bytes``; bench --mode pp evidence)."""
        return self.kv_pool_bytes() // self.pp

    def kv_scale_bytes(self) -> int:
        """Per-page scale overhead bytes (0 for bf16)."""
        n = kv_quant.scale_nbytes(self.kv)
        if self.draft_kv is not None:
            n += kv_quant.scale_nbytes(self.draft_kv)
        return n

    @property
    def num_free(self) -> int:
        return len(self._free)

    def publish_states(self) -> None:
        """The ``mlt_engine_pool_pages{class=,state=}`` gauges: reads of
        kept counts."""
        free, idle = len(self._free), self._idle_cached
        self._m_pages["free"].set(free)
        self._m_pages["cached_idle"].set(idle)
        self._m_pages["referenced"].set(self.num_pages - 1 - free - idle)

    @property
    def num_evictable(self) -> int:
        """Cached pages no request references — reclaimable on demand.
        A read: the count is kept where references and ``cached`` change
        (what a walk ``sum(refcounts[p] == 0 for p in cached)`` would
        give, tests/test_prefix_cache.py holds the two equal)."""
        return self._idle_cached

    def set_cached(self, page: int, cached: bool) -> None:
        """The prefix cache registers ``page`` (a node now owns it) or
        gives it up (evicted; the caller puts it on the free list)."""
        if cached:
            self.cached.add(page)
        else:
            self.cached.remove(page)
        if self.refcounts[page] == 0:
            self._idle_cached += 1 if cached else -1

    @property
    def num_available(self) -> int:
        """Pages an ``alloc`` could produce right now (free + evictable)."""
        return self.num_free + self.num_evictable

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages at refcount 1, or None if free + evictable
        can't satisfy the request.  Evicts cached-idle pages (LRU,
        leaf-first) only when the free list alone runs short."""
        # the free list first: the tick's page grants come here once a
        # row, and this path reads no clock and opens no span
        evicted = 0
        if n > len(self._free):
            evicted = self._reclaim(n)
            if n > len(self._free):
                return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            assert self.refcounts[p] == 0 and p not in self.cached
            self.refcounts[p] = 1
        if obs_registry.publishing():
            self._m_alloc["free"].inc(n - evicted)
            if evicted:
                self._m_alloc["evict"].inc(evicted)
        return pages

    def _reclaim(self, n: int) -> int:
        """``alloc``'s slow path: the free list is short of ``n`` pages.
        Evicts the shortfall in cached-idle pages onto it if there are as
        many (a read of the kept count: a grant that cannot be served
        evicts nothing and reads no clock), and returns how many it
        evicted.  What it costs is what its victims cost, one or two heap
        entries each, not the size of the trie.  One ``pool-reclaim`` span
        a call (inside the caller's ``engine-admit`` or ``engine-plan``)
        with the eviction's zero-length ``pool-evict`` inside, so a
        device idle gap that is an eviction is named in a capture."""
        short = n - len(self._free)
        with obs_trace.span("pool-reclaim", want=n, free=len(self._free),
                            cached=len(self.cached)):
            if short > self.num_evictable or self.evict_hook is None:
                return 0
            t0 = time.perf_counter()
            freed = self.evict_hook(short)
            if obs_registry.publishing():
                self._m_scan["evict"].inc(time.perf_counter() - t0)
            self._free.extend(freed)
            if freed:
                self.reclaimed = True
            return len(freed)

    def free_evicted(self, pages: Sequence[int]) -> None:
        """Pages the prefix cache gave up outside this pool's own
        ``_reclaim`` (a node evicted for the other class) go free."""
        self._free.extend(pages)

    def incref(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert p != NULL_PAGE
            if self.refcounts[p] == 0 and p in self.cached:
                self._idle_cached -= 1
            self.refcounts[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page.  Unreferenced pages return to the
        free list unless the prefix cache still holds them (those stay
        cached-idle until matched again or evicted)."""
        idle = []
        for p in pages:
            assert p != NULL_PAGE, "null page is never allocated"
            self.refcounts[p] -= 1
            assert self.refcounts[p] >= 0, f"page {p} over-released"
            if self.refcounts[p] == 0:
                (idle if p in self.cached else self._free).append(p)
        if idle:
            self._idle_cached += len(idle)
            if self.idle_hook is not None:
                self.idle_hook(idle)

    # ---- cross-replica page transfer (ISSUE 19, serving/handoff/) ----

    def export_pages(self, pages: Sequence[int]) -> Dict[str, np.ndarray]:
        """Gather ``pages`` from every storage leaf to the host: ONE
        batched ``device_get`` over all leaves (values, scale rows, draft
        cache), so a multi-page export pays one transfer sync.  What
        comes back are the wire's LOGICAL leaves (ops/kv_quant.kv_to_leaves:
        ``k``, ``v``, for a quantized pool ``k.q`` / ``k.scale`` / ``v.q`` /
        ``v.scale``, a speculating pool's ``draft_*`` beside them), bytes
        verbatim — exactly the set a receiving pool must install for a
        migrated page to be bit-identical to a locally prefilled one;
        the physical row never leaves this pool.  The caller must hold
        page refs on ``pages`` and serialize against tick dispatch (the
        engine's ``_drive_lock``) — ticks rebind the pool arrays with
        donated buffers."""
        assert not self.latent, "the handoff carries K/V pools"
        ids = np.asarray(list(pages), np.int32)
        pools = self._pools()
        host = jax.device_get(
            [jax.tree.map(lambda a: a[:, ids], pool) for _, pool, _ in pools])
        leaves: Dict[str, np.ndarray] = {}
        for (prefix, _, d), got in zip(pools, host):
            leaves.update(kv_quant.kv_to_leaves(got, d, prefix))
        return leaves

    def import_pages(self, pages: Sequence[int],
                     leaves: Dict[str, np.ndarray]) -> None:
        """Install exported leaf bytes into freshly allocated ``pages``
        VERBATIM — quantized leaves set values and scales directly,
        never re-quantizing, so the imported page is byte-identical to
        the sender's (tests/test_handoff.py round-trip).  Leaf names,
        dtypes and shapes must match this pool's logical leaves exactly
        (a bf16 pool cannot install an int8 export; a speculating
        sender's draft leaves need a speculating receiver).  Caller
        serializes against tick dispatch, same as :meth:`export_pages`."""
        ids = np.asarray(list(pages), np.int32)
        quant = kv_quant.is_quantized(self.kv)
        want: Dict[str, Tuple] = {}
        for prefix, pool, d in self._pools():
            arr = kv_quant.values_of(pool)
            lead = (arr.shape[0], len(ids))
            heads = arr.shape[-1] // (2 * d)
            for side in "kv":
                name = prefix + side + (".q" if quant else "")
                want[name] = (arr.dtype, lead + (arr.shape[2], heads, d))
                if quant:
                    want[prefix + side + ".scale"] = (
                        pool.scale.dtype, lead + (heads,))
        if sorted(want) != sorted(leaves):
            raise ValueError(
                f"handoff leaves {sorted(leaves)} do not match this "
                f"pool's storage leaves {sorted(want)} "
                f"(kv_dtype={self.kv_dtype!r}, "
                f"draft={'yes' if self.draft_kv is not None else 'no'})")
        for name, (dtype, shape) in want.items():
            val = leaves[name]
            if tuple(val.shape) != shape or val.dtype != dtype:
                raise ValueError(
                    f"handoff leaf {name!r} is {val.dtype}{val.shape}, "
                    f"pool needs {dtype}{shape}")

        def _install(pool, prefix):
            rows = kv_quant.kv_from_leaves(leaves, quant, prefix)
            return jax.tree.map(
                lambda a, r: a.at[:, ids].set(jnp.asarray(r)), pool, rows)

        self.kv = _install(self.kv, "")
        if self.draft_kv is not None:
            self.draft_kv = _install(self.draft_kv, "draft_")


class StatePool(PagedKVPool):
    """The pool of the layers that keep a recurrent STATE and no keys:
    power retention's (ops/retention.py: ``kv`` is ``ops/retention.State``,
    leaves ``s [layers, slots + 1, nkv, d, D]`` and ``z [layers, slots + 1,
    nkv, 1, D]``) or a hybrid's linear layers': gated-delta ones
    (ops/gated_delta.py: ``DeltaState``, ``s [layers, slots + 1, hv, dk,
    dv]`` and the conv's tail ``conv [layers * (slots + 1), (width - 1) *
    channels]``) beside latent rows, or Mamba-2 ones (ops/mamba2.py:
    ``MambaState``, ``s [layers, slots + 1, n, h * p]`` and the same tail)
    beside K/V pages; in float32; or the tails of a stack of gated short
    convolutions (``ConvTail``: the tail ALONE, in the activations' dtype);
    indexed by STATE SLOT; ``layers``: how many of the model's layers keep
    their state here (all of them, unless told).  The allocator is the page
    pool's, a slot standing where a page stood: a sequence holds exactly
    ONE from admission to its end, whatever its length, so with as many
    slots as the engine has decode slots nothing ever runs dry, nothing is
    granted while a sequence decodes, and nothing is shared, cached or
    evicted.  Slot 0 is the null slot, as page 0 is the null page: a dead
    row's table names it and the tick touches no state for it.  A slot is
    not cleared when it changes hands: the first row of a sequence stands
    at position 0, and the tick's program takes a zero state for the run
    that starts there whatever the slot held (``ops/retention.tick_runs``:
    no launch of its own)."""

    def __init__(self, cfg, slots: int, page_size: int,
                 layers: Optional[int] = None,
                 page_class: Optional[str] = None):
        assert (cfg.model.retention or cfg.model.delta
                or cfg.model.mamba or cfg.model.short_conv), (
            "a state pool holds power retention's states, a hybrid's "
            "linear layers' (gated-delta or Mamba-2) or short "
            "convolutions' tails")
        super().__init__(cfg, slots + 1, page_size, layers=layers,
                         page_class=page_class, state=True)

    @property
    def kv_statics(self) -> Tuple:
        first = self.kv[0]           # ``s``; a tail-only class's ``conv``
        return ("kv", "state", str(first.dtype), first.shape[-1])

    def kv_pool_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.kv)

    def kv_scale_bytes(self) -> int:
        return 0


@dataclasses.dataclass
class SeqMemory:
    """What ONE sequence holds of ONE class.  ``pages``: a block's page at
    the block's place, ``NULL_PAGE`` where a window has moved past it
    (``first`` blocks so far); a state class's one slot.  Blocks below
    ``keep`` are the prefix cache's, the rest the sequence's own:
    ``private`` of them live, never more than ``max`` (what the ledger
    holds for it).  ``row``: its row of the class's table, -1 while the
    sequence waits or prefills."""

    pages: List[int] = dataclasses.field(default_factory=list)
    first: int = 0
    keep: int = 0
    private: int = 0
    max: int = 0
    row: int = -1


class ClassMemory:
    """What the engine holds of ONE class: ``committed`` is what the
    admitted sequences may still take beyond their own pages (the sum of
    ``max - private``), and admission keeps ``free + evictable >=
    committed + watermark``, so a grant to a sequence in flight cannot
    fail.  ``window``: the keys a query sees (0: every one); such a class
    takes its prompt's pages a tick's rows at a time, never more of its
    own than ``cap``, and gives a page back once the window has moved past
    it.  A class without one takes the prompt's pages at admission and a
    page a boundary a decode row crosses.  A state class takes ONE slot,
    once: its table is one entry wide and its ledger keeps no slack."""

    def __init__(self, pool: PagedKVPool, slots: int, width: int, *,
                 window: int = 0, cap: Optional[int] = None,
                 watermark: int = 0):
        self.pool = pool
        self.name = pool.page_class or "full"
        self.state = isinstance(pool, StatePool)
        self.window = window
        self.width = 1 if self.state else width
        self.cap = self.width if cap is None else cap
        self.watermark = 0 if self.state else watermark
        self.table = np.zeros((slots, self.width), np.int32)
        self.committed = 0

    # ---- admission ----

    def demand(self, keep: int, cow: int, fill: int,
               total: int) -> Tuple[int, int]:
        """(pages granted at admission, the most of its own it ever holds)
        for a sequence of ``total`` pages whose first ``keep`` are shared:
        the copy-on-write page (``cow``, 0 or 1) and, without a window, the
        ``fill`` pages of its prompt and first decode rows."""
        if self.state:
            return 1, 1
        most = min(self.cap, total - keep)
        return (cow if self.window else min(cow + fill, most)), most

    def can_admit(self, keep: int, cow: int, fill: int, total: int) -> bool:
        """Whether the pool and the ledger allow such a :meth:`demand`."""
        need, most = self.demand(keep, cow, fill, total)
        return (self.pool.num_available - need
                >= self.committed + most - need + self.watermark)

    def admit(self, matched: Sequence[int], keep: int, cow: int, fill: int,
              total: int) -> SeqMemory:
        """The ``matched`` pages (the prefix cache's, the caller holds their
        references) and what :meth:`demand` grants now, fresh, the rest
        booked in the ledger.  After :meth:`can_admit`."""
        need, most = self.demand(keep, cow, fill, total)
        fresh = self.pool.alloc(need)
        assert fresh is not None, "can_admit() holds the ledger"
        self.committed += most - need
        first = 0       # the blocks a matched window had already left
        while first < len(matched) and matched[first] == NULL_PAGE:
            first += 1
        return SeqMemory(list(matched) + fresh, first, keep, need, most)

    def undo(self, matched: Sequence[int]) -> None:
        """A refused admission gives its matched references back."""
        self.pool.release([p for p in matched if p != NULL_PAGE])

    def drop_shared(self, mem: SeqMemory) -> None:
        """The copy-on-write copy (the page after it) has landed."""
        self.pool.release([mem.pages.pop(mem.keep)])

    # ---- while the sequence lives ----

    def grant(self, mem: SeqMemory, last_block: int) -> Optional[int]:
        """A page of the sequence's own, off the ledger, for every block up
        to ``last_block`` it holds none for yet; how many those were, None
        where the pool cannot (ledger-unreachable)."""
        last, had = min(last_block, self.width - 1), len(mem.pages)
        while len(mem.pages) <= last:
            got = self.pool.alloc(1)
            if got is None:
                return None
            mem.pages.append(got[0])
            mem.private += 1
            self.committed -= 1
        if mem.row >= 0 and len(mem.pages) > had:
            self.table[mem.row, had:len(mem.pages)] = mem.pages[had:]
        return len(mem.pages) - had

    def slide(self, mem: SeqMemory, qpos: int) -> int:
        """No query at ``qpos`` or later sees a key of the blocks before the
        one holding key ``qpos - window + 1``: their pages go back (those
        the prefix cache registered stay cached-idle) and the ledger holds
        a page again for each of the sequence's own.  A tick already
        launched may still read them: it runs before any tick that writes
        what a later grant makes of them.  Returns the pages released."""
        if not self.window:
            return 0
        lo = mem.first
        first = min(max(0, qpos - self.window + 1) // self.pool.page_size,
                    len(mem.pages))
        if first <= lo:
            return 0
        gone, own = [], 0
        for i in range(lo, first):
            p = mem.pages[i]
            if p != NULL_PAGE:
                gone.append(p)
                own += i >= mem.keep
                mem.pages[i] = NULL_PAGE
        mem.first = first
        mem.private -= own
        self.committed += own
        self.pool.release(gone)
        if mem.row >= 0:
            # the mirror only: the row's queries start behind these blocks,
            # and the next upload anything else asks for carries the nulls
            self.table[mem.row, lo:first] = NULL_PAGE
        return len(gone)

    def held(self, mem: SeqMemory) -> int:
        """Pages the sequence holds, whole window."""
        return len(mem.pages) - mem.first

    def trim(self, mem: SeqMemory) -> None:
        """It takes no further page: the ledger's rest for it returns."""
        self.committed -= mem.max - mem.private
        mem.max = mem.private

    def release(self, mem: SeqMemory) -> int:
        """Every page the sequence holds goes back, with what the ledger
        still held for it; returns how many pages those were."""
        pages = [p for p in mem.pages if p != NULL_PAGE]
        self.trim(mem)
        self.pool.release(pages)
        mem.pages, mem.row = [], -1
        mem.first = mem.keep = mem.private = mem.max = 0
        return len(pages)

    # ---- the slot's table row ----

    def install(self, slot: int, mem: SeqMemory) -> None:
        self.table[slot] = NULL_PAGE
        self.table[slot, :len(mem.pages)] = mem.pages
        mem.row = slot

    def clear(self, slot: int) -> None:
        self.table[slot] = NULL_PAGE

    def snapshot(self, dead: Sequence[int] = ()) -> np.ndarray:
        """A copy for a tick (the mirror moves again before the tick that
        reads the upload has run), the ``dead`` rows null."""
        table = self.table.copy()
        table[list(dead)] = NULL_PAGE
        return table


class _TrieNode:
    __slots__ = ("key", "page", "wpage", "parent", "children", "last_use",
                 "depth")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.depth = 0 if parent is None else parent.depth + 1
        # the block's page in the window class of a patterned model's pool
        # (NULL_PAGE: none, or evicted while the full class's page stays)
        self.wpage = NULL_PAGE
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.last_use = 0


class PrefixCache:
    """Host-side radix/trie over page-aligned token chunks -> pool pages.

    Each node owns one FULL page of prompt K/V, keyed by that page's
    ``page_size`` token ids; a path from the root spells a prompt prefix.
    ``match`` walks the trie and takes a pool reference on every matched
    page (the caller's block table will point at them); ``insert`` registers
    a freshly prefilled request's full prompt pages so later requests can
    share them.  Because a request that matches a page has, by
    construction, matched ALL its ancestors too, a refcount-0 node's
    descendants are also refcount-0 — so eviction can always proceed
    leaf-first through cached-idle subtrees, and ``PagedKVPool.num_evictable``
    (the pool's kept count of cached pages at refcount 0) is exactly the
    number of reclaimable pages.

    Eviction takes the idle LEAF (no child, refcount 0) with the lowest
    ``last_use``.  Those leaves are kept in that order in a heap
    (``_idle``), entered where a node becomes one: the pool releases its
    page's last reference while it has no child (``note_idle``), its last
    child is evicted while it is idle, or ``insert`` ends on it
    unreferenced.  Nothing is taken out when a node stops being one
    (``match`` references it, ``insert`` hangs a child on it or stamps it
    anew): an entry is checked where it is popped and dropped if its node
    is gone, has a child, is referenced or was stamped since, so ``evict``
    looks at one or two entries a victim whatever the trie holds.  Order
    of entry is not order of use, hence a heap and no queue.  Every
    ``match`` / ``insert`` stamps one root path with a fresh clock value
    and only the deepest node of a path can be childless, so no two idle
    leaves share a ``last_use`` and the order is total.  A pool that
    never runs dry never pops: the heap is rebuilt from the trie when it
    outgrows ``2 * len(self) + 64`` entries, which keeps it O(nodes) at
    an amortised constant a push.

    **Two page classes** (``wpool``: a patterned model's window class, its
    layers seeing ``window`` keys).  A node names its block's page in each
    class, and the window class's may be gone while the full class's stays:
    a sequence gives a window page back once its window has moved past it
    (registered ones go cached-idle in their class), and the window pool
    evicts its idle pages in use order WHATEVER their place in the trie
    (``evict_window``: a heap of its own, the shallower of two pages of one
    stamp first, since a match needs the pages before its end).  A window
    page referenced means its node's full page referenced (a sequence
    holds every full page of its context), so evicting a node frees both.
    ``match_classes`` returns the longest page-aligned length whose full
    pages are all present AND whose window pages cover the ``window`` keys
    before its end; a longer match that the window class cannot serve is
    shortened to that (recomputing a window layer's keys needs the layers
    below at those positions, so a gap cannot be filled in).
    """

    def __init__(self, pool: PagedKVPool, page_size: int,
                 wpool: Optional[PagedKVPool] = None,
                 window: Optional[int] = None):
        self.pool = pool
        self.wpool = wpool
        self.window = window
        self.page_size = page_size
        self.root = _TrieNode(None, NULL_PAGE, None)
        self._nodes: Dict[int, _TrieNode] = {}  # page id -> node
        self._clock = 0
        # (last_use, entry number, node): the number only keeps two
        # entries of one node and stamp from comparing nodes
        self._idle: List[Tuple[int, int, _TrieNode]] = []
        self._entry = itertools.count()
        pool.evict_hook = self.evict
        pool.idle_hook = self.note_idle
        reg = obs_registry.get_registry()
        if wpool is not None:
            self._wnodes: Dict[int, _TrieNode] = {}  # window page -> node
            # (last_use, depth, entry number, node)
            self._widle: List[Tuple[int, int, int, _TrieNode]] = []
            wpool.evict_hook = self.evict_window
            wpool.idle_hook = self.note_widle
            self._m_wevicted = reg.counter(
                "mlt_engine_prefix_window_evicted_pages_total",
                help="cached-idle pages of the WINDOW class the prefix "
                     "cache gave up to a grant (their nodes keep the full "
                     "class's page); equals mlt_engine_pool_alloc_pages_"
                     "total{class=\"window\",source=\"evict\"}")
            self._m_shortened = reg.counter(
                "mlt_engine_prefix_match_shortened_total",
                help="prefix matches cut short of the full class's pages "
                     "because the window class no longer held the pages "
                     "before the match's end",
                labels={"by": "window"})
        self._m_evicted = reg.counter(
            "mlt_engine_prefix_evicted_pages_total",
            help="cached-idle pages the prefix cache gave up to a grant")
        self._m_scanned = reg.counter(
            "mlt_engine_prefix_evict_scanned_nodes_total",
            help="entries of the idle-leaf order looked at to pick "
                 "eviction victims, stale ones included; over the evicted "
                 "pages: the work one eviction costs (1-2, whatever the "
                 "trie holds)")
        self._m_rebuilds = reg.counter(
            "mlt_engine_prefix_idle_rebuilds_total",
            help="times the idle-leaf order outgrew twice the trie and "
                 "was rebuilt from it (stale entries of a pool that "
                 "rarely evicts)")

    def __len__(self) -> int:
        return len(self._nodes)

    def _key(self, tokens: Sequence[int], i: int) -> Tuple[int, ...]:
        ps = self.page_size
        return tuple(tokens[i * ps:(i + 1) * ps])

    def match(self, tokens: Sequence[int], max_pages: int) -> List[int]:
        """Longest cached prefix of ``tokens`` in whole pages (capped at
        ``max_pages``); takes one pool ref per matched page."""
        self._clock += 1
        node, pages = self.root, []
        for i in range(max_pages):
            child = node.children.get(self._key(tokens, i))
            if child is None:
                break
            child.last_use = self._clock
            pages.append(child.page)
            node = child
        self.pool.incref(pages)
        return pages

    def window_first(self, n_pages: int) -> int:
        """The first block whose window-class page a sequence still needs
        once ``n_pages`` whole pages of it are cached: the one holding the
        oldest key the query at the last cached position can see."""
        return max(0, n_pages * self.page_size - self.window) // self.page_size

    def match_classes(self, tokens: Sequence[int], max_pages: int
                      ) -> Tuple[List[int], List[int]]:
        """``match`` for a pool with a window class: (full-class pages,
        window-class pages), one pool ref each, the second list as long as
        the first with ``NULL_PAGE`` for the blocks the window has left
        behind (a sequence's window table keeps the block's place)."""
        self._clock += 1
        node, path, run, runs = self.root, [], 0, []
        for i in range(max_pages):
            child = node.children.get(self._key(tokens, i))
            if child is None:
                break
            run = run + 1 if child.wpage != NULL_PAGE else 0
            path.append(child)
            runs.append(run)
            node = child
        m = len(path)
        while m and runs[m - 1] < m - self.window_first(m):
            m -= 1
        if m < len(path) and obs_registry.publishing():
            self._m_shortened.inc()
        first = self.window_first(m)
        for nd in path[:m]:
            nd.last_use = self._clock
        pages = [nd.page for nd in path[:m]]
        wpages = [nd.wpage for nd in path[first:m]]
        self.pool.incref(pages)
        self.wpool.incref(wpages)
        return pages, [NULL_PAGE] * first + wpages

    def match_lists(self, tokens: Sequence[int],
                    max_pages: int) -> List[List[int]]:
        """:meth:`match` as ONE list a page class, in the pools' order."""
        if self.wpool is None:
            return [self.match(tokens, max_pages)]
        return list(self.match_classes(tokens, max_pages))

    def insert_lists(self, tokens: Sequence[int],
                     lists: Sequence[Sequence[int]], n_pages: int) -> int:
        """:meth:`insert` of one list a page class."""
        return self.insert(tokens, lists[0], n_pages, *lists[1:])

    def insert(self, tokens: Sequence[int], pages: Sequence[int],
               n_pages: int, wpages: Optional[Sequence[int]] = None) -> int:
        """Register the first ``n_pages`` full pages of a prefilled prompt;
        pages already cached at a position keep the incumbent (the
        request's duplicate page simply stays private).  ``wpages``: the
        same blocks' window-class pages (``NULL_PAGE`` where the sequence
        gave one back); a node without one takes the sequence's where the
        sequence holds the node's full page too, or can replace it.
        Returns the number of pages newly cached."""
        self._clock += 1
        node, added = self.root, 0
        for i in range(n_pages):
            key = self._key(tokens, i)
            child = node.children.get(key)
            if child is None:
                p = pages[i]
                if p in self._nodes:  # defensive: one node per page
                    break
                child = _TrieNode(key, p, node)
                node.children[key] = child
                self._nodes[p] = child
                self.pool.set_cached(p, True)
                added += 1
            if wpages is not None and child.wpage == NULL_PAGE and (
                    i < len(wpages) and wpages[i] != NULL_PAGE
                    and wpages[i] not in self._wnodes):
                if child.page != pages[i]:
                    # an incumbent that lost its window page: a prefix
                    # nobody can match past.  Where nobody holds its full
                    # page either, the sequence's own pair of pages takes
                    # its place (a window page referenced means its node's
                    # full page referenced); else the pair stays private
                    if (self.pool.refcounts[child.page] != 0
                            or pages[i] in self._nodes):
                        child.last_use = self._clock
                        node = child
                        continue
                    del self._nodes[child.page]
                    self.pool.set_cached(child.page, False)
                    self.pool.free_evicted([child.page])
                    child.page = pages[i]
                    self._nodes[child.page] = child
                    self.pool.set_cached(child.page, True)
                child.wpage = wpages[i]
                self._wnodes[child.wpage] = child
                self.wpool.set_cached(child.wpage, True)
            child.last_use = self._clock
            node = child
        # the walk's end is the one node it may leave an idle leaf under a
        # stamp the heap has not seen: a new page nobody references, or
        # an incumbent's that its request's duplicate did not replace
        if node is not self.root:
            self._push_if_idle_leaf(node)
        return added

    def note_idle(self, pages: Sequence[int]) -> None:
        """The pool's ``idle_hook``: a release left ``pages`` cached at
        refcount 0.  Of a retired request's chain only the deepest page
        is a leaf."""
        for p in pages:
            self._push_if_idle_leaf(self._nodes[p])

    def _push_if_idle_leaf(self, node: _TrieNode) -> None:
        if node.children or self.pool.refcounts[node.page] != 0:
            return
        heapq.heappush(self._idle,
                       (node.last_use, next(self._entry), node))
        if len(self._idle) > 2 * len(self._nodes) + 64:
            self._rebuild_idle()

    def _rebuild_idle(self) -> None:
        """Drop the stale entries: the heap anew from the trie's idle
        leaves, in place (``evict`` may be popping from this list)."""
        self._idle[:] = [
            (n.last_use, next(self._entry), n) for n in self._nodes.values()
            if not n.children and self.pool.refcounts[n.page] == 0]
        heapq.heapify(self._idle)
        if obs_registry.publishing():
            self._m_rebuilds.inc()

    def evict(self, n: int) -> List[int]:
        """Reclaim up to ``n`` cached-idle pages, least-recently-used
        leaves first (removing a leaf may expose its parent, which
        competes from then on under its own ``last_use``)."""
        freed: List[int] = []
        scanned = 0
        idle, nodes, refcounts = self._idle, self._nodes, self.pool.refcounts
        while len(freed) < n and idle:
            last_use, _, victim = heapq.heappop(idle)
            scanned += 1
            if (nodes.get(victim.page) is not victim or victim.children
                    or refcounts[victim.page] != 0
                    or victim.last_use != last_use):
                continue  # stale: it stopped being this idle leaf
            parent = victim.parent
            del parent.children[victim.key]
            del nodes[victim.page]
            self.pool.set_cached(victim.page, False)
            freed.append(victim.page)
            if victim.wpage != NULL_PAGE:
                # idle too: whoever held it held the full page
                self._drop_wpage(victim)
                self.wpool.free_evicted([victim.wpage])
                victim.wpage = NULL_PAGE
            if parent is not self.root:
                self._push_if_idle_leaf(parent)
        # what the call did, as one zero-length event inside the pool's
        # ``pool-reclaim``: the numbers are known only now
        with obs_trace.span("pool-evict", evicted=len(freed),
                            scanned=scanned):
            pass
        if obs_registry.publishing():
            self._m_evicted.inc(len(freed))
            self._m_scanned.inc(scanned)
        return freed

    # ---- the window class ----

    def _drop_wpage(self, node: _TrieNode) -> None:
        assert self.wpool.refcounts[node.wpage] == 0
        del self._wnodes[node.wpage]
        self.wpool.set_cached(node.wpage, False)

    def note_widle(self, pages: Sequence[int]) -> None:
        """The window pool's ``idle_hook``: a release (a window that moved
        on, a retirement) left ``pages`` cached at refcount 0; every one
        is evictable, a leaf or not."""
        for p in pages:
            node = self._wnodes[p]
            heapq.heappush(self._widle, (node.last_use, node.depth,
                                         next(self._entry), node))
        if len(self._widle) > 2 * len(self._wnodes) + 64:
            self._widle[:] = [
                (n.last_use, n.depth, next(self._entry), n)
                for p, n in self._wnodes.items()
                if self.wpool.refcounts[p] == 0]
            heapq.heapify(self._widle)

    def evict_window(self, n: int) -> List[int]:
        """Reclaim up to ``n`` cached-idle pages of the window class, least
        recently used first; their nodes stay, with the full class's page.
        An entry whose node was stamped since (a match that took the full
        page and no longer needed this one) goes back under its new stamp;
        one whose page is gone or referenced is dropped."""
        freed: List[int] = []
        scanned = 0
        idle, refcounts = self._widle, self.wpool.refcounts
        while len(freed) < n and idle:
            last_use, _, _, node = heapq.heappop(idle)
            scanned += 1
            wp = node.wpage
            if (wp == NULL_PAGE or self._wnodes.get(wp) is not node
                    or refcounts[wp] != 0):
                continue
            if node.last_use != last_use:
                heapq.heappush(idle, (node.last_use, node.depth,
                                      next(self._entry), node))
                continue
            self._drop_wpage(node)
            node.wpage = NULL_PAGE
            freed.append(wp)
        with obs_trace.span("pool-evict", evicted=len(freed),
                            scanned=scanned, page_class="window"):
            pass
        if obs_registry.publishing():
            self._m_wevicted.inc(len(freed))
        return freed
