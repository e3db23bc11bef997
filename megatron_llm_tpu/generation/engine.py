"""Continuous-batching decode engine on a prefix-cached paged KV cache.

The legacy serving shape (generation/api.InferenceEngine) is the paper's:
one request at a time, a dense ``[L, b, max_seq, nkv, d]`` cache allocated
per call, and a program compiled per (batch, max_seq) bucket.  This engine
is the TPU-serving shape the Ragged-Paged-Attention and Gemma-on-Cloud-TPU
studies (PAPERS.md) converge on: keep ONE fixed-shape decode program
resident, keep its batch full, and never compute the same prefix twice.

What a sequence keeps lives in generation/pools.py, below this file:

* **Paged KV pool and prefix cache** (:class:`PagedKVPool`,
  :class:`PrefixCache`): all in-flight sequences share one pool of
  REFERENCE-COUNTED pages, a sequence owning an ordered page list (its
  block table; page 0 is the *null page* that idle rows write and nobody
  attends).  A host-side trie over page-aligned token chunks lets
  admission take a cached prefix's pages and prefill only the suffix; a
  shared page a request's first tick would rewrite is copied first
  (copy-on-write), and pages nobody references STAY cached until the free
  list runs dry, then go LRU leaf-first.

* **One per-sequence memory a class** (:class:`ClassMemory`): a request
  holds ``_mem``, one :class:`SeqMemory` a class in the order of the
  engine's ``_classes``.  A uniform model has one; a layer pattern a full
  and a window class; a model whose layers keep a constant-size recurrent
  state a sequence (:class:`StatePool`: power retention) ONE state slot
  from admission to its end, its table one entry wide; a hybrid
  (gated-delta or Mamba-2 layers beside attention) pages AND a slot,
  granted together or not at all.  The class owns the host mirror of the
  tick's table, HOW a sequence's memory is granted, slid and released, and
  the commitment ledger: admission takes only the prompt-suffix pages
  (plus the first decode page), decode one page a boundary it crosses, and
  the ledger keeps ``free + evictable`` at least the worst-case remaining
  demand of every admitted request (plus ``page_watermark``), so a slot in
  flight never deadlocks on the pool — admission defers instead.  This
  file decides WHEN, in loops over ``zip(self._classes, req._mem)`` that
  name no kind of memory.  With a state class prefill stops before a
  prompt's last token for the whole stack (a state cannot take a token
  twice: :meth:`_fill_end`), preemption drops the state and the resume
  prefills again, and the prefix cache is off.  What a kind of memory does
  not carry refuses in a sentence (``refuse_unserved``).

* **Chunked prefill**: the uncovered suffix runs in fixed-size chunks that
  write K/V through the block table and attend through it too
  (ops/paged_attention.paged_attention_prefill — the prefix-length-aware
  prefill-against-block-table mode, Pallas kernel on TPU).  The scheduler
  packs chunk rows into the decode tick instead of stalling the whole batch
  for a whole prompt, so queued requests' time-to-first-token stops
  scaling with the longest admitted prompt.  Chunk boundaries are aligned
  to absolute-position multiples of ``prefill_chunk`` and the attended page
  horizon is bucketed per chunk, so the K/V bits a chunk produces depend
  only on (tokens, absolute positions) — a cache hit replays bitwise the
  pages a cold prefill would compute (the cache-on/off parity contract,
  tests/test_prefix_cache.py).

* **Slots + fixed shapes**: the tick runs ``max_slots`` decode rows every
  time, active or not.  Block tables, positions, per-slot sampling params
  and per-slot PRNG keys are *traced* inputs, so the tick compiles once
  per bucketed count of prompt rows it carries (generation/ragged.py).
  Slots mid prefill keep their device block-table row at the null page,
  so tick writes from not-yet-active rows land in garbage that is never
  attended.

* **The tick**: one fused jitted step (generation/ragged.py) — embed the
  decode rows' tokens and the packed prompt rows, write each row's K/V
  into its current page, paged attention over block tables (Pallas kernel
  on TPU, jnp gather fallback elsewhere — ops/paged_attention.py),
  per-slot sampling (sampling.sample_per_slot), token log-probs.  Pool
  buffers are donated, so the cache updates in place.

* **Scheduling control plane** (generation/scheduling/): every scheduling
  DECISION — admission order, the per-tick prefill-chunk budget,
  preemption victims, load shedding — delegates to a pluggable
  :class:`~megatron_llm_tpu.generation.scheduling.SchedulerPolicy`
  (``--sched_policy``: ``fcfs`` default / ``priority`` / ``slo``), while
  the MECHANISMS (slots here, pages and ledgers in the classes) do not.
  Preemption works by page release: the victim's finished KV pages are
  parked in the prefix trie, its pages released, and the request
  re-queued — re-admission matches the pages back out of the trie and
  resume is bitwise-identical to never having been preempted.  Admission
  control is metrics-driven: overload 503s carry an EMA-drain Retry-After,
  per-priority queue bounds gate the classes independently, and the slo
  policy sheds requests whose deadline is already unmeetable.

* **Speculative decoding** (generation/speculative/, ``--spec_k`` +
  ``--spec_draft``): a small draft model proposes up to k tokens per
  tick, the target verifies all k+1 positions in ONE forward (the k+1
  query positions flattened into the batch so every op is the decode
  tick's shape — per-row bits are batch-size invariant, which is what
  makes greedy speculation BITWISE-identical to ``spec_k=0``), and a
  lossless acceptance rule emits 1..k+1 tokens.  Draft K/V lives in the
  SAME pool (one page id addresses both caches), so block tables,
  refcounts, the commitment ledger, the prefix trie, COW and
  preemption-by-page-release all govern both models unchanged.

Threading: ``submit`` may be called from any thread (e.g. concurrent HTTP
handlers — generation/server.py); device work happens on whichever thread
drives :meth:`step`, either the built-in background loop (:meth:`start`) or
a caller loop (:meth:`run_until_idle`).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import traceback
from collections import deque
from typing import (Dict, List, NamedTuple, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config.arguments import check_prefill_chunk
from megatron_llm_tpu.core.parallel_state import PP_AXIS, TP_AXIS
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation import placement
from megatron_llm_tpu.generation.launch import call_tick
from megatron_llm_tpu.generation.pools import (  # noqa: F401 — re-exported
    NULL_PAGE,
    ClassMemory,
    PagedKVPool,
    PrefixCache,
    SeqMemory,
    StatePool,
    refuse_unserved,
)
from megatron_llm_tpu.generation.scheduling import (
    RequestShed,
    SchedulerPolicy,
    SchedulerState,
    get_policy,
)
from megatron_llm_tpu.observability import compiles as obs_compiles
from megatron_llm_tpu.observability import flight as obs_flight
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.observability import trace as obs_trace
from megatron_llm_tpu.observability.profiler import (
    ProfileTrigger, profile_dir)
from megatron_llm_tpu.generation.tokenization import detokenize_generations
from megatron_llm_tpu.models.language_model import (
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.generation.ragged import block_driver, decode_order
from megatron_llm_tpu.ops import kv_quant
from megatron_llm_tpu.ops.paged_attention import PagedState
from megatron_llm_tpu.ops.pallas.paged_attention import tile_shares


def _request_key(seed: int) -> np.ndarray:
    """The two words of ``jax.random.PRNGKey(seed)`` (threefry), worked out
    on the host.  As a device program the seeding would queue behind the
    tick in flight, and the apply that activates the request would wait
    for that tick to end (tests/test_tick_lag.py pins the equality)."""
    hi = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, seed & 0xFFFFFFFF], np.uint32)


def _bucket_up(n: int, bucket: int = gen.BUCKET) -> int:
    return -(-n // bucket) * bucket


class EngineOverloaded(RuntimeError):
    """Submit-time backpressure: the request queue is at capacity.

    The server maps this to a structured 503 with a ``Retry-After`` header
    instead of queueing unboundedly (generation/server.py).  ``retry_after``
    is metrics-driven — the engine's EMA drain estimate for the current
    queue depth, not a constant — and ``info`` carries the queue snapshot
    the server includes in the 503 body."""

    def __init__(self, msg: str, retry_after: float = 1.0,
                 info: Optional[dict] = None):
        super().__init__(msg)
        self.retry_after = retry_after
        self.info = info or {}


@dataclasses.dataclass
class EngineRequest:
    """One in-flight generation; ``result()`` blocks until finished."""

    prompt: List[int]
    max_new_tokens: int
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0
    termination_id: Optional[int] = None
    use_eod_for_termination: bool = True
    stop_on_double_eol: bool = False
    stop_on_eol: bool = False
    seed: Optional[int] = None
    return_log_probs: bool = False
    # scheduling (generation/scheduling/): priority class (0 = most
    # urgent, the `priority` policy) and soft deadlines (the `slo`
    # policy); all ignored by fcfs
    priority: int = 1
    ttft_deadline_ms: Optional[float] = None
    tpot_deadline_ms: Optional[float] = None
    # distributed tracing (ISSUE 12): the X-MLT-Trace-Id the router or
    # caller minted; correlates this request across router spans,
    # replica spans and flight records ("" = untraced direct submit)
    trace_id: str = ""
    # disaggregated serving (ISSUE 19): stop after chunked prefill and
    # park in the `handoff` phase with page refs held — the export path
    # (prefill_and_export) ships the pages and retires the request; the
    # request never takes a decode tick
    prefill_only: bool = False

    # engine-filled state
    generated: List[int] = dataclasses.field(default_factory=list)
    log_probs: List[float] = dataclasses.field(default_factory=list)
    prompt_log_probs: Optional[List[float]] = None
    finished: bool = False
    error: Optional[str] = None
    shed: bool = False  # dropped by the scheduler, never served
    shed_retry_after: float = 1.0
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)
    # what it holds of each class of per-sequence memory, in the order of
    # the engine's ``_classes``; empty until its first admission
    _mem: List[SeqMemory] = dataclasses.field(default_factory=list,
                                              repr=False)
    _step: int = 0  # decode ticks taken (== len(generated))
    # scheduler state: queued -> prefill -> decode -> finished
    _phase: str = dataclasses.field(default="queued", repr=False)
    _slot: int = dataclasses.field(default=-1, repr=False)
    _fill_pos: int = dataclasses.field(default=0, repr=False)
    _hit_tokens: int = dataclasses.field(default=0, repr=False)
    _t_submit: float = dataclasses.field(default=0.0, repr=False)
    _t_first: float = dataclasses.field(default=0.0, repr=False)
    _t_done: float = dataclasses.field(default=0.0, repr=False)
    _seqno: int = dataclasses.field(default=0, repr=False)
    _preemptions: int = dataclasses.field(default=0, repr=False)
    # PRNG key resolved at FIRST activation and pinned: a preempted
    # request resumes the same sampling stream (fold_in(key, _step))
    _key: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    # speculative decoding: acceptance EMA drives the per-slot adaptive
    # depth (starts optimistic; shrinks when the draft keeps missing)
    _spec_ema: float = dataclasses.field(default=1.0, repr=False)
    # flight record (observability/flight.py); the shared null record
    # when the recorder is disabled, so every call site stays branch-free
    _flight: object = dataclasses.field(
        default=obs_flight.NULL_RECORD, repr=False)
    # token streaming (serving/streaming/): the per-request emission
    # queue submit_stream attached, fed by the apply/retire paths under
    # _lock; None = plain request/response submit
    _stream: object = dataclasses.field(default=None, repr=False)

    def result(self, timeout: Optional[float] = None):
        """Wait for completion; returns (full token list, gen log-probs)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.shed:
            raise RequestShed(self.error or "request shed",
                              retry_after=self.shed_retry_after)
        if self.error:
            raise RuntimeError(self.error)
        return list(self.prompt) + self.generated, list(self.log_probs)

    @property
    def seq_tokens(self) -> List[int]:
        """Prompt + tokens generated so far — the effective prompt a
        preempted request re-admits with (fresh requests: the prompt)."""
        return list(self.prompt) + self.generated

    @property
    def ttft(self) -> Optional[float]:
        """Seconds from submit to first generated token (bench telemetry)."""
        if self._t_first == 0.0:
            return None
        return self._t_first - self._t_submit

    @property
    def latency(self) -> Optional[float]:
        """Seconds from submit to retirement (bench telemetry)."""
        if self._t_done == 0.0:
            return None
        return self._t_done - self._t_submit


class _Launched(NamedTuple):
    """A launch the host has not applied yet (an entry of
    ``ContinuousBatchingEngine._inflight``): the slots it ran, the requests
    those slots held then, its device tokens and log-probs, its launch
    time.  A row is dropped at apply when its slot no longer holds its
    request as launched (retired, preempted or failed meanwhile:
    ``_row_live``)."""

    active: List[int]
    reqs: List[EngineRequest]
    toks: object
    logps: object
    t0: float
    epochs: List[int]  # each request's ``_preemptions`` at the launch
    no: int = 0        # the tick's number: ``tick=`` of its spans
    spans: Sequence = ()  # the prompt chunks it packed: (req, start, end)
    n_bucket: int = 0  # its compiled prompt-row capacity (0: decode-only)
    # a verify tick's (accepted, counts, k_eff); ``toks``/``logps`` then
    # hold the emitted blocks ``[b, K+1]``
    spec: Optional[Tuple] = None
    # an expert model's tick: what its router did, ``[2]`` on the device
    # (assignments, distinct experts touched; generation/ragged.py)
    moe: object = None
    # a looped stack's tick: its sampled rows' exit masses, ``[slots,
    # passes]`` on the device
    loop_mass: object = None


class ContinuousBatchingEngine:
    """Shared-tick decode over a prefix-cached paged pool."""

    @obs_compiles.startup_phase("engine-build")
    def __init__(self, cfg, params, tokenizer=None, *,
                 max_slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 page_watermark: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 sched_policy=None,
                 spec_k: Optional[int] = None,
                 spec_draft=None,
                 spec_adaptive: Optional[bool] = None,
                 prefill_budget: Optional[int] = None,
                 flight_records: Optional[int] = None,
                 flight_events: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        inf = cfg.inference
        self.cfg = cfg
        pick = lambda given, name: (  # noqa: E731
            given if given is not None else getattr(inf, name))
        # before anything is placed or resolved: a sentence, not a
        # sharding error from the middle of start-up
        refuse_unserved(cfg, kv_dtype=pick(kv_dtype, "kv_dtype"), mesh=mesh,
                        draft=bool(pick(spec_k, "spec_k")))
        # page classes (models/transformer.py ``pool_classes``).  A class
        # may keep a constant-size state a sequence (:class:`StatePool`):
        # in place of pages (power retention: the slot is the one "page" a
        # sequence holds) or beside a page class (a hybrid)
        from megatron_llm_tpu.models.transformer import (
            layer_kinds,
            pool_classes,
        )

        classes = pool_classes(cfg)
        self.state = any(cls.state for cls in classes)
        state_only = all(cls.state for cls in classes)
        if inf.int8_weights:
            # same decode-weight quantization contract as api.InferenceEngine
            from megatron_llm_tpu.ops.quant import quantize_layer_weights_int8

            params = quantize_layer_weights_int8(params)
        # Tensor-parallel serving: params shard by the parallel/tp.py rules
        # (qkv/lm_head column-parallel, dense/fc2 row-parallel, vocab-
        # parallel embedding), the KV pool shards over the heads dim, and
        # every jitted program (tick / prefill chunk / page copy) follows
        # its committed input shardings — XLA inserts the row-parallel
        # all-reduces. mesh=None (or an all-1 mesh) is today's single-chip
        # engine, byte for byte.
        self.mesh = mesh
        self._tp = mesh.shape.get(TP_AXIS, 1) if mesh is not None else 1
        # Pipeline-parallel serving (ISSUE 20, parallel/pp_serve.py): a
        # pp>1 mesh runs the tick's layer stack as pp stages over
        # microbatched rows, with the paged pool sharded per stage over
        # its own layers.  pp == 1 (or no mesh) resolves the context to
        # None — the flag is inert and every program is byte-for-byte
        # today's TP-only engine.
        self._pp = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
        # --tp_overlap ring (parallel/overlap.py): the decode/ragged-tick
        # forwards route their row-parallel projections through the
        # chunked collective-matmul ring.  None = off (byte-for-byte
        # today's implicitly-inserted collectives); resolves to None at
        # tp == 1 regardless of the flag (single-chip degradation).
        # --vocab_ring rides in the same context: the head GEMM's logits
        # all-gather becomes an all-gather matmul ring (ISSUE 20).
        from megatron_llm_tpu.parallel import overlap as tp_overlap_mod
        from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

        self._overlap = tp_overlap_mod.overlap_params(cfg, mesh)
        self._overlap_mode = ("ring" if self._overlap is not None
                              and self._overlap.ring_rows else "off")
        self._vocab_ring = bool(self._overlap is not None
                                and self._overlap.vocab_ring)
        self._ppc = pp_serve_mod.serve_params(cfg, mesh)
        if self._pp > 1:
            # pp stages own contiguous layer slices of params AND pool —
            # checked before param placement so the friendly assert wins
            # over the sharding divisibility ValueError
            assert cfg.model.num_layers % self._pp == 0, (
                f"num_layers {cfg.model.num_layers} not divisible by "
                f"pp {self._pp}")
        if mesh is not None:
            from megatron_llm_tpu.parallel.tp import param_shardings

            m = cfg.model
            if self._tp > 1:
                from megatron_llm_tpu.models.language_model import (
                    padded_vocab_size,
                )

                assert m.num_attention_heads % self._tp == 0, (
                    f"attention heads {m.num_attention_heads} not divisible "
                    f"by tp {self._tp}")
                assert padded_vocab_size(m.vocab_size, cfg) % self._tp == 0, (
                    "padded vocab not divisible by tp")
            params = jax.device_put(params, param_shardings(mesh, params))
            self._repl = NamedSharding(mesh, P())
        else:
            self._repl = None
        self.params = params
        self.tokenizer = tokenizer
        self.max_slots = max_slots or inf.max_batch_slots
        self.page_size = page_size or inf.page_size
        self.max_seq = (max_seq or inf.engine_max_seq
                        or min(cfg.data.seq_length,
                               cfg.model.max_position_embeddings))
        assert self.max_seq <= cfg.model.max_position_embeddings
        assert gen.BUCKET % self.page_size == 0, (
            "page_size must divide the prefill bucket so bucketed prefills "
            "scatter whole pages")
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else getattr(inf, "prefill_chunk", gen.BUCKET))
        check_prefill_chunk(self.prefill_chunk, self.page_size)
        use_cache = (prefix_cache if prefix_cache is not None
                     else getattr(inf, "prefix_cache", True))
        self.page_watermark = (page_watermark if page_watermark is not None
                               else getattr(inf, "page_watermark", 0))
        self.max_queue = (max_queue if max_queue is not None
                          else getattr(inf, "max_queued_requests", 256))
        # scheduling policy (generation/scheduling/): decisions delegate
        # to it, mechanisms stay here.  A string resolves through the
        # registry; tests may hand a policy instance directly.
        sched = (sched_policy if sched_policy is not None
                 else getattr(inf, "sched_policy", "fcfs"))
        if isinstance(sched, SchedulerPolicy):
            self.policy = sched
        else:
            self.policy = get_policy(sched)(
                aging_s=getattr(inf, "sched_aging_s", 5.0),
                preemption=getattr(inf, "sched_preemption", True))
        # per-priority queue bounds ("0:64,2:16"); classes without a quota
        # share only the global max_queue bound
        self._quota: Dict[int, int] = {}
        for part in (getattr(inf, "sched_quota", None) or "").split(","):
            if part.strip():
                prio, bound = part.split(":")
                self._quota[int(prio)] = int(bound)
        # speculative decoding (generation/speculative/): a draft model
        # proposes spec_k tokens per tick, the target verifies all of them
        # in one flattened-batch forward, and a lossless acceptance rule
        # keeps the longest agreed prefix.  spec_k=0 is today's one-token
        # tick, byte for byte (the spec path never compiles).
        self.spec_k = spec_k if spec_k is not None else getattr(
            inf, "spec_k", 0)
        self.spec_adaptive = (spec_adaptive if spec_adaptive is not None
                              else getattr(inf, "spec_adaptive", True))
        self.draft_cfg = self.draft_params = None
        if self.spec_k:
            from megatron_llm_tpu.generation.speculative import (
                DraftModel,
                check_draft_compat,
                resolve_draft,
            )

            draft = (spec_draft if spec_draft is not None
                     else getattr(inf, "spec_draft", None))
            if draft is None:
                raise ValueError(
                    "spec_k > 0 requires a draft model (--spec_draft)")
            if isinstance(draft, str):
                draft = resolve_draft(draft, cfg)
            elif isinstance(draft, tuple):
                draft = DraftModel(*draft)
            check_draft_compat(cfg, draft.cfg, max_seq=self.max_seq)
            draft_params = draft.params
            if mesh is not None:
                from megatron_llm_tpu.parallel.tp import param_shardings

                draft_params = jax.device_put(
                    draft_params, param_shardings(mesh, draft_params))
            self.draft_cfg, self.draft_params = draft.cfg, draft_params
        # the word-embedding tables in rows (generation/placement.py): where
        # the device's default layout of one is not, every program that
        # looks a token up would write the whole table out again
        self.params, self.draft_params = placement.tables_in_rows(
            self.params, self.draft_params)
        if mesh is None:
            # parameters COMMITTED to their device (a re-laid table is)
            # commit every tick's outputs, the pool and the carried tokens
            # among them: the pools and what the engine uploads are born
            # there too, or jit compiles each tick program once a mixture
            # of committed and uncommitted operands
            self._repl = placement.committed_to(self.params)
        budget_cap = (prefill_budget if prefill_budget is not None
                      else getattr(inf, "prefill_budget", 0))
        # prompt tokens a tick may prefill; the policy's token budget is
        # capped here.  Nobody set it: the decode width in whole chunks,
        # so a wide engine fills its slots at the pace it empties them
        # (the tick streams the weights once whatever its rows) and an
        # engine of at most one chunk of slots keeps the
        # one-chunk-a-tick interleave.
        if budget_cap:
            self._prefill_cap = max(self.prefill_chunk, int(budget_cap))
        else:
            self._prefill_cap = _bucket_up(self.max_slots,
                                           self.prefill_chunk)
        # compiled prefill-row capacity of the ragged tick (a geometry
        # static, like max_slots): ONE compiled launch per tick carries
        # the decode slots, the speculative-verify blocks AND up to
        # prefill_rows prefill-chunk rows (generation/ragged.py)
        self.prefill_rows = self._prefill_cap
        # distinct prefilling requests packable into one tick — the
        # compressed-table capacity of the ragged program (one table row
        # per request; rows of a request share it)
        self._pre_tables_cap = self.prefill_rows // self.prefill_chunk + 1
        # a state class's "table" is one entry wide: the sequence's slot
        self.pages_per_seq = (1 if state_only
                              else -(-self.max_seq // self.page_size))
        num_pages = (num_pages or inf.kv_pool_pages
                     or self.max_slots * self.pages_per_seq + 1)
        # quantized paged KV (ISSUE 13, ops/kv_quant.py): int8/fp8 pages
        # with per-page scales multiply the concurrent slots a fixed pool
        # byte budget carries; bf16 (default) is byte-for-byte today's
        # engine.  Target AND draft caches quantize together — one flag,
        # one storage discipline for every page.
        self.kv_dtype = (kv_dtype if kv_dtype is not None
                         else getattr(inf, "kv_dtype", "bf16"))
        if self._pp > 1 and self.draft_cfg is not None:
            assert self.draft_cfg.model.num_layers % self._pp == 0, (
                f"draft num_layers {self.draft_cfg.model.num_layers} "
                f"not divisible by pp {self._pp}")
        # one pool a class (its leaf, free list and reference counts) and
        # over it the class's table, ledger and operations on a request's
        # ``_mem`` (``ClassMemory``).  A uniform model has ONE, with the
        # mesh, the draft and unlabelled counters; ``pool`` is the first,
        # ``wpool`` a pattern's window class, ``spool`` the state class
        self.wpool: Optional[PagedKVPool] = None
        self.spool: Optional[StatePool] = None
        self._window = 0
        self.window_pages_cap: Optional[int] = None
        self._pools: List[PagedKVPool] = []
        self._classes: List[ClassMemory] = []  # guarded by _lock
        many = len(classes) > 1
        for cls in classes:
            named = (dict(layers=cls.layers(cfg), page_class=cls.name)
                     if many else {})
            window, cap, pages = 0, None, num_pages
            if cls.state:
                pl = self.spool = StatePool(cfg, self.max_slots,
                                            self.page_size, **named)
            else:
                if many and cls.window is not None:
                    window = self._window = int(cls.window)
                    # pages one sequence holds of it at most: the window's
                    # own (ceil(window / page), + 1 where it starts inside
                    # a page), the one its next position opens, and while
                    # it prefills the pages of one tick's rows
                    cap = self.window_pages_cap = (
                        -(-window // self.page_size) + 2
                        + self.prefill_rows // self.page_size)
                    pages = (inf.kv_window_pool_pages or self.max_slots
                             * min(self.pages_per_seq, cap) + 1)
                pl = PagedKVPool(
                    cfg, pages, self.page_size, kv_dtype=self.kv_dtype,
                    **named, **({} if many else dict(
                        mesh=mesh, draft_cfg=self.draft_cfg)))
                if window:
                    self.wpool = pl
            self._pools.append(pl)
            self._classes.append(ClassMemory(
                pl, self.max_slots, self.pages_per_seq, window=window,
                cap=cap, watermark=self.page_watermark))
        self.pool = self._pools[0]
        if mesh is None and self._repl is not None:
            for pl in self._pools:
                pl.kv = jax.device_put(pl.kv, self._repl)
            if self.draft_cfg is not None:
                self.pool.draft_kv = jax.device_put(self.pool.draft_kv,
                                                    self._repl)
        if self.state:
            if use_cache:
                print("[engine] the prefix cache is off for a state pool: "
                      "a sequence's past is one recurrent state, which a "
                      "trie of pages does not hold at a page's boundary",
                      flush=True)
            use_cache = False
        self.cache = (PrefixCache(self.pool, self.page_size, self.wpool,
                                  self._window or None)
                      if use_cache else None)

        # host-side slot state + scheduler queues: every attribute marked
        # "guarded by _lock" below is shared between submitter threads,
        # the background scheduler and drive-through callers — graftcheck's
        # lock-discipline rule enforces the with-blocks / '# holds'
        # annotations (docs/guide/static-analysis.md)
        s = self.max_slots
        self._positions = np.zeros((s,), np.int32)    # guarded by _lock
        self._tokens = np.zeros((s,), np.int32)       # guarded by _lock
        self._temperature = np.ones((s,), np.float32)  # guarded by _lock
        # idle slots decode greedy — guarded by _lock
        self._top_k = np.ones((s,), np.int32)
        self._top_p = np.zeros((s,), np.float32)      # guarded by _lock
        self._keys = np.zeros((s, 2), np.uint32)      # guarded by _lock
        self._steps = np.zeros((s,), np.int32)        # guarded by _lock
        # guarded by _lock
        self._slots: List[Optional[EngineRequest]] = [None] * s

        self._queue: deque = deque()  # guarded by _lock
        # admitted, prompt not yet filled — guarded by _lock
        self._prefill_q: deque = deque()
        self.window_pages_released = 0
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        # serializes device-driving (step) across caller threads; state
        # mutation is under _lock, device dispatch under _drive_lock
        self._drive_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False  # guarded by _lock

        # ragged tick executables keyed by bucketed live-prefill-row
        # count — bounded at 1 + prefill_rows // prefill_chunk entries
        self._ragged_fns: Dict[int, object] = {}
        # the return_log_probs carve-out's chunk executables, keyed by
        # (rows, page horizon)
        self._chunk_fns: Dict[Tuple[int, int], object] = {}
        self._copy_fn = None
        # device mirror of the per-slot arrays; rebuilt from the host copies
        # whenever admission/retirement changes the slot layout
        self._dev_state: Optional[Tuple] = None  # guarded by _lock
        self._dirty = True  # guarded by _lock
        # launched-but-unapplied ticks, oldest first (:class:`_Launched`):
        # at most one between two steps (the step launches the next tick
        # before it applies this one) — guarded by _lock
        self._inflight: deque = deque()
        # the ``carried`` operand of a tick that takes no token from a
        # tick in flight
        self._no_carry = self._asarray(
            np.zeros((self.max_slots,), np.bool_))
        # when the last ragged tick's results reached the host
        self._last_fetch_t = 0.0  # guarded by _lock
        # wall time the last device dispatch call returned (driver-thread
        # only; reads/writes serialize under _drive_lock)
        self._last_dispatch_end: Optional[float] = None
        # steps that raised in the scheduler loop (:meth:`_fail_all`)
        self.failures, self._blocks = 0, block_driver(self)
        # tick/cache telemetry for the decode bench
        self.ticks = 0
        self.ticked_tokens = 0
        # attention-program launches in the tick phase (ISSUE 11): ONE
        # compiled program per tick, plus the scoring chunk of a
        # return_log_probs prompt when one is prefilling.
        # last_tick_launches is the most recent step's count — the
        # single-launch claim tests assert on.
        self.tick_launches = 0
        self.last_tick_launches = 0
        # capacity telemetry (ISSUE 13): the high-water mark of
        # concurrently-decoding slots — THE "concurrent users per chip"
        # number the fixed-pool-bytes capacity bench and /health report
        self.peak_active_slots = 0  # guarded by _lock
        self.prefill_tokens_computed = 0  # rows pushed through prefill
        # what the router of an expert model did, summed over ticks and
        # expert layers (mlt_engine_moe_*; zero for dense models)
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        # ... and of those, what fell to the experts this chip holds
        self.moe_held_assignments = 0
        self.moe_held_experts_touched = 0
        # a looped stack (loop_steps > 1): the sampled rows' exit masses a
        # pass (mlt_engine_loop_exit_mass_total; no other model has it)
        self.loop_exit_mass = np.zeros((cfg.model.loop_steps,), np.float64)
        self.prefix_hit_tokens = 0
        self.prefix_miss_tokens = 0
        self.cow_copies = 0
        # scheduler telemetry (bench_decode --mode slo + /health payload)
        self.preemptions = 0
        self.shed_requests = 0
        self.deadline_misses = 0
        # speculative-decoding telemetry (bench_decode --mode spec +
        # /health spec payload)
        self.spec_ticks = 0
        self.spec_draft_tokens = 0     # drafts proposed (sum of k_eff)
        self.spec_accepted_tokens = 0  # drafts the target accepted
        self.spec_emitted_tokens = 0   # tokens emitted by spec ticks
        # submit order, stable policy tie-break — guarded by _lock
        self._seqno = 0
        # decode-tick wall EMA — guarded by _lock
        self._ema_tick_s: Optional[float] = None
        # inter-retire EMA — guarded by _lock
        self._ema_retire_s: Optional[float] = None
        self._last_retire_t: Optional[float] = None  # guarded by _lock
        # submit-to-first-token EMA: the replica's REAL first-token time
        # (published in /health so the router's slo_aware predictions use
        # measured TTFT, not time-to-response) — guarded by _lock
        self._ema_ttft_s: Optional[float] = None
        # flight recorder (ISSUE 12, observability/flight.py): one
        # bounded event log + latency decomposition per request, served
        # on /debug/requests and dumped by the watchdog.  0 records
        # disables it (every call site degrades to the null record).
        n_rec = (flight_records if flight_records is not None
                 else getattr(inf, "flight_records", 256))
        n_ev = (flight_events if flight_events is not None
                else getattr(inf, "flight_events", 64))
        self.flight = obs_flight.FlightRecorder(
            capacity=n_rec, events_per_request=n_ev, enabled=n_rec > 0)
        obs_flight.set_recorder(self.flight)
        # on-demand jax.profiler windows of the live engine (GET
        # /profile?ticks=N on the serving port): armed from a handler
        # thread, started and stopped by the scheduler loop at step
        # boundaries; a tick is the trigger's "step".  Same flags and
        # layout as the trainer's (--profile_dir, --profile_max_captures)
        self.profile_trigger = ProfileTrigger(
            os.path.join(profile_dir(cfg.logging), "ondemand"),
            max_captures=cfg.logging.profile_max_captures)
        # label sets ever published — guarded by _lock
        self._queued_prios: Set[int] = set()
        # registry instruments, resolved once (observability/registry.py):
        # per-tick updates must stay dict-free on the scheduler thread
        reg = obs_registry.get_registry()
        self._m_requests = reg.counter(
            "mlt_engine_requests_total", help="generations submitted")
        self._m_ticks = reg.counter(
            "mlt_engine_ticks_total", help="fused decode ticks run")
        self._m_failures = reg.counter(
            "mlt_engine_failures_total",
            help="engine steps that raised (lowering, compile or runtime "
                 "failure); every request in flight answered 500")
        self._m_tokens = reg.counter(
            "mlt_engine_ticked_tokens_total",
            help="tokens appended to requests (a causal slot-step: one)")
        self._m_active = reg.gauge(
            "mlt_engine_active_slots", help="decode slots occupied")
        self._m_queued = reg.gauge(
            "mlt_engine_queued_requests", help="requests awaiting a slot")
        self._m_free_pages = reg.gauge(
            "mlt_engine_free_pages", help="KV pool pages free")
        self._m_hit_tokens = reg.counter(
            "mlt_engine_prefix_hit_tokens_total",
            help="prompt tokens served from the prefix cache")
        self._m_miss_tokens = reg.counter(
            "mlt_engine_prefix_miss_tokens_total",
            help="prompt tokens that had to be prefilled")
        self._m_pages_cached = reg.gauge(
            "mlt_engine_pages_cached",
            help="pool pages registered in the prefix cache")
        self._m_cow = reg.counter(
            "mlt_engine_pages_cow_copies_total",
            help="copy-on-write page copies (shared page would be written)")
        self._m_prefill_tokens = reg.counter(
            "mlt_engine_prefill_tokens_total",
            help="token rows pushed through prefill")
        self._m_launches = reg.counter(
            "mlt_engine_tick_launches_total",
            help="attention-program launches in the tick phase (one per "
                 "non-idle tick, plus a return_log_probs prompt's scoring "
                 "chunk)")
        self._m_prefill_per_tick = reg.histogram(
            "mlt_engine_prefill_tokens_per_tick",
            help="prompt tokens prefilled per tick (token-level "
                 "prefill_budget control; observed on ticks that prefill)",
            buckets=[16.0, 32.0, 64.0, 128.0, 192.0, 256.0, 512.0,
                     1024.0])
        self._m_multi_chunk = reg.counter(
            "mlt_engine_prefill_multi_chunk_ticks_total",
            help="ticks that prefilled more prompt tokens than one "
                 "prefill_chunk (over mlt_engine_tick_kind_total"
                 "{kind=\"prefill\"}: how often the pacing packs a tick)")
        self._m_moe_assignments = reg.counter(
            "mlt_engine_moe_assignments_total",
            help="router assignments (rows x topk, every row a tick ran, "
                 "summed over the expert layers); 0 for dense models")
        self._m_moe_touched = reg.counter(
            "mlt_engine_moe_experts_touched_total",
            help="distinct experts that received a row, summed over ticks "
                 "and expert layers: with the assignments, the rows an "
                 "expert's GEMM ran on")
        self._m_loop_mass = None
        if cfg.model.loop_steps > 1:
            self._m_loop_mass = [
                reg.counter(
                    "mlt_engine_loop_exit_mass_total",
                    help="a looped stack: the exit distribution's mass at "
                         "this pass, summed over the sampled rows (the "
                         "steps' series sum to the rows sampled)",
                    labels={"step": str(t + 1)})
                for t in range(cfg.model.loop_steps)]
        self._m_preempt = reg.counter(
            "mlt_engine_preemptions_total",
            help="decoding requests preempted by page release")
        self._m_shed = reg.counter(
            "mlt_engine_shed_total",
            help="queued requests shed (unmeetable deadline / load)")
        # every *_seconds histogram here shares one geometric ladder: a
        # median or a tail read off it is within +-15%
        lat = obs_registry.LATENCY_BUCKETS
        self._m_ttft = reg.histogram(
            "mlt_engine_ttft_seconds",
            help="submit-to-first-token latency of retired requests",
            buckets=lat)
        self._m_miss_ttft = reg.counter(
            "mlt_engine_deadline_miss_total",
            help="retired requests that missed a declared deadline",
            labels={"kind": "ttft"})
        self._m_miss_tpot = reg.counter(
            "mlt_engine_deadline_miss_total",
            help="retired requests that missed a declared deadline",
            labels={"kind": "tpot"})
        # honest TTFT decomposition (ISSUE 12): where retired requests'
        # first-token latency actually went.  The phase-attributed
        # deadline-miss children ({kind,phase}) are created lazily at
        # miss time; the {kind}-only children above stay the totals.
        self._m_queue_wait = reg.histogram(
            "mlt_engine_queue_wait_seconds",
            help="submit-to-admission wait of retired requests (flight-"
                 "recorder queued-phase bucket)", buckets=lat)
        self._m_prefill_compute = reg.histogram(
            "mlt_engine_prefill_compute_seconds",
            help="prefill-phase seconds of retired requests (admission "
                 "to decode activation)", buckets=lat)
        self._m_preempted_s = reg.histogram(
            "mlt_engine_preempted_seconds",
            help="seconds retired requests spent preempted (observed "
                 "only for requests that were preempted at least once)",
            buckets=lat)
        # the host gap is the wall time between one tick launch returning
        # and the next being dispatched — scheduling + emission fetch +
        # apply.  The fetch is a wait for the device, so this is NOT host
        # work: the phase histogram below is
        self._m_host_gap = reg.histogram(
            "mlt_engine_host_gap_seconds",
            help="wall time between consecutive tick-program dispatches: "
                 "one whole scheduler cycle less the dispatch itself. It "
                 "INCLUDES the fetch's wait for the device, so it is "
                 "about one tick long while the host's work (read "
                 "mlt_engine_tick_phase_seconds: admit, plan, launch, "
                 "apply) fits beside the running tick, and longer once "
                 "it does not",
            buckets=lat)
        # where one ragged tick's wall time goes, phase by phase, at the
        # boundaries of the engine-* spans (one observation a tick each:
        # admit, plan and launch when it is dispatched, fetch and apply
        # when its results land, a step later while ticks follow on)
        self._m_phase = {
            ph: reg.histogram(
                "mlt_engine_tick_phase_seconds",
                help="wall seconds of one ragged tick by phase: admit "
                     "(queue to slots), plan (prefill packing, paging, "
                     "slot-state upload), launch (argument conversion + "
                     "dispatch), fetch (waiting for the device and the "
                     "result's way back), apply (tokens to requests and "
                     "stream queues, retirement, gauges, and the wait "
                     "to get the interpreter lock back from the handler "
                     "threads it woke: a request's first token, its "
                     "end). Fetch and apply of a tick run after the "
                     "next tick's launch, beside it on the device",
                labels={"phase": ph}, buckets=lat)
            for ph in ("admit", "plan", "launch", "fetch", "apply")}
        # the scheduler thread's own CPU clock (time.thread_time, a system
        # call: four reads a tick) over the same host work: wall minus CPU
        # is the time the thread stood still inside its own work
        self._m_host_cpu = {
            side: reg.histogram(
                "mlt_engine_tick_host_cpu_seconds",
                help="CPU seconds of the scheduler thread in one ragged "
                     "tick's host work: dispatch = admit + plan + launch "
                     "(one stretch on the thread), apply = apply. The "
                     "sums of mlt_engine_tick_phase_seconds over those "
                     "four phases less these: waiting for the "
                     "interpreter behind the stream writer and the "
                     "handler threads, for the engine's lock, or inside "
                     "a blocking upload. fetch is a wait by design and "
                     "has no CPU reading",
                labels={"side": side}, buckets=lat)
            for side in ("dispatch", "apply")}
        # plan's three jobs, one observation a launched tick each; what
        # plan holds beyond their sum is its two waits for the engine's
        # lock and the row count of the launch
        self._m_plan_part = {
            part: reg.histogram(
                "mlt_engine_plan_part_seconds",
                help="wall seconds of one ragged tick's plan phase by "
                     "part: prefill (packing prompt rows, a scoring "
                     "chunk), pages (the decode rows' page grants; an "
                     "eviction once the pool is dry), upload (the "
                     "slot-state arrays to the device, when dirty)",
                labels={"part": part}, buckets=lat)
            for part in ("prefill", "pages", "upload")}
        self._m_dry_ticks = reg.counter(
            "mlt_engine_pool_dry_ticks_total",
            help="ragged ticks during whose admit or plan a page grant "
                 "had to evict (the pool's free list had run dry); over "
                 "mlt_engine_ticks_total the share of ticks that paid "
                 "for an eviction. A patterned model also counts them by "
                 "page class (class=)")
        self._m_paged_rows = reg.counter(
            "mlt_engine_paged_rows_total",
            help="live rows (decode, verify, prompt) of the launched "
                 "ragged ticks")
        self._m_paged_walks = reg.counter(
            "mlt_engine_paged_walks_total",
            help="page walks those rows cost the paged kernel a layer of "
                 "the class that keeps every key: rows of one tile of 8 on "
                 "ONE table (a prompt chunk's, a block's denoise and commit "
                 "rows) are walked once together, any other row once "
                 "(ops/pallas/paged_attention.tile_shares, its walks()); "
                 "rows over walks is how often the shared walk engages")
        self._m_paged_carried = reg.counter(
            "mlt_engine_paged_carried_walks_total",
            help="of those walks, the ones whose first block's copies the "
                 "walk before them in the call starts while it computes its "
                 "last block (paged_attention.walk_order; TileShares."
                 "carried()): every walk of a call but its first. Carried "
                 "over walks is the share of walks that start with their "
                 "pages on the way")
        # learned sparse attention (models/sparse_mla.py): what the indexer
        # scored and what the attention then read, by the program's own rule
        self._m_sparse = {
            "rows": reg.counter(
                "mlt_engine_sparse_rows_total",
                help="live rows of the launched ticks that SELECTED: whose "
                     "context is longer than index_topk (a shorter one "
                     "attends all of it); 0 for a model with no indexer"),
            "scored": reg.counter(
                "mlt_engine_sparse_keys_scored_total",
                help="index keys those rows' indexer scored (a row's whole "
                     "context), times the attention layers"),
            "attended": reg.counter(
                "mlt_engine_sparse_keys_attended_total",
                help="latent rows those rows then attended (index_topk a "
                     "row), times the attention layers; over the keys "
                     "scored, the share of its context a query reads"),
        } if cfg.model.index_topk else None
        self._m_paged_seen = reg.counter(
            "mlt_engine_paged_blocks_seen_total",
            help="compute blocks (the kernel's step: several pages) under "
                 "the masks of those rows, summed over rows, times the "
                 "attention layers of the rows' page class")
        self._m_paged_fetched = reg.counter(
            "mlt_engine_paged_blocks_fetched_total",
            help="compute blocks the kernel's page walks fetched for them: "
                 "blocks of one table's rows once a tile, blocks that rows "
                 "of several tables name alike (sequences on one cached "
                 "prefix, laid side by side by the tick) once a span, every "
                 "other block once a row (paged_attention.tile_shares); "
                 "fetched over seen is the share of its rows' blocks the "
                 "kernel reads")
        # the state class's own (zero for a paged model): what the tick's
        # state sweep ran on, counted on the host from each tick's plan
        self.state_recomputed_tokens = 0
        self._m_state = {
            "rows": reg.counter(
                "mlt_engine_state_rows_total",
                help="live rows (decode and prompt) of the launched ticks "
                     "of a model with a state class (alone or a hybrid's)"),
            "touches": reg.counter(
                "mlt_engine_state_touches_total",
                help="state reads and writes those rows cost a layer and "
                     "KV head: ONE a run (a sequence's consecutive rows of "
                     "one tick), so rows over touches is what a prompt run "
                     "amortises; 1.0 for decode-only ticks"),
            "steps": reg.counter(
                "mlt_engine_state_steps_total",
                help="passes over a resident state the sweep made of those "
                     "rows a layer and block of heads, by the rule of the "
                     "state class's ops module: one a decode row; a prompt "
                     "run's tiles where the sweep takes a run in the "
                     "chunked form (Mamba-2: ops/mamba2.sweep_steps), its "
                     "rows where it walks them"),
            "resets": reg.counter(
                "mlt_engine_state_resets_total",
                help="runs that started a sequence (position 0): the "
                     "slot's state taken as zero inside the tick's own "
                     "program"),
            "recomputed_tokens": reg.counter(
                "mlt_engine_state_recomputed_tokens_total",
                help="tokens prefilled again because a preemption dropped "
                     "their state (a page pool resumes from the prefix "
                     "cache instead)")}
        reg.gauge("mlt_engine_state_pool_bytes",
                  help="device bytes of the recurrent-state pool (float32 "
                       "S and z of every layer, KV head and slot, the "
                       "null slot included; 0 for a paged model)"
                  ).set(self.spool.kv_pool_bytes() if self.state else 0)
        # what the paged kernel walks of a page class, for the host's
        # count of its blocks: (attention layers, their window, bytes of
        # this engine's share of a token's row)
        self._walked = []
        for cls, pl in zip(classes, self._pools):
            if not cls.state:
                leaf = kv_quant.values_of(pl.kv)
                self._walked.append((
                    leaf.shape[0], layer_kinds(cfg)[cls.places[0]].window,
                    leaf.shape[-1] * leaf.dtype.itemsize // self._tp))
        self._m_dry_class = {
            pl.page_class: reg.counter(
                "mlt_engine_pool_dry_ticks_total",
                help="ragged ticks during whose admit or plan a page grant "
                     "of this page class had to evict",
                labels={"class": pl.page_class})
            for pl in self._pools if pl.page_class is not None}
        self._m_seq_pages = [
            reg.counter(
                "mlt_engine_seq_pages_sum",
                help="pages of this class that the decoding sequences "
                     "held, summed over sequences and applied ticks; over "
                     "mlt_engine_seq_ticks_total the mean pages a live "
                     "sequence holds, whole window",
                labels={"class": cls.name})
            for cls in self._classes]
        self._m_seq_ticks = reg.counter(
            "mlt_engine_seq_ticks_total",
            help="decoding sequences summed over applied ticks (the "
                 "divisor of mlt_engine_seq_pages_sum)")
        self._m_slid = reg.counter(
            "mlt_engine_window_pages_released_total",
            help="window-class pages a LIVE sequence gave back because "
                 "its window moved past them (0 for a uniform model)")
        self._m_moe_held = reg.counter(
            "mlt_engine_moe_held_assignments_total",
            help="router assignments whose expert this chip holds and "
                 "ran (moe_experts_held; all of them where every expert "
                 "is held)")
        self._m_moe_held_touched = reg.counter(
            "mlt_engine_moe_held_experts_touched_total",
            help="distinct HELD experts that received a row, summed over "
                 "ticks and expert layers")
        self._m_apply_lag = {
            lag: reg.counter(
                "mlt_engine_tick_apply_lag_total",
                help="ragged ticks by how they were applied: 1 = the next "
                     "tick had been dispatched first (the host worked "
                     "beside the device), 0 = nothing was queued behind "
                     "it (speculative engines, a scoring chunk, the last "
                     "tick before idle)",
                labels={"lag": lag}) for lag in ("0", "1")}
        self._m_tick_kind = {
            k: reg.counter(
                "mlt_engine_tick_kind_total",
                help="ragged ticks by kind: prefill = the launch carried "
                     "prompt rows (a larger program), decode = it did not",
                labels={"kind": k}) for k in ("decode", "prefill")}
        self._m_inflight = reg.gauge(
            "mlt_engine_inflight_ticks",
            help="device ticks launched but not yet applied (the "
                 "ragged tick running beside the host)")
        # token streaming (ISSUE 18, serving/streaming/): live
        # subscriptions + incremental events shed by slow consumers
        # (drop-to-terminal — the terminal event is never shed)
        self._stream_subs = 0  # live submit_stream queues — guarded by _lock
        # an apply put tokens into some stream's queue and the stream
        # writer has not been kicked for them yet
        self._stream_dirty = False  # guarded by _lock
        # the server's stream writer (serving/streaming/writer.py): called
        # once an applied tick, outside _lock; must never block
        self.stream_kick = None
        self._m_stream_subs = reg.gauge(
            "mlt_engine_stream_subscribers",
            help="live submit_stream subscriptions (emission queues "
                 "attached to in-flight requests)")
        self._m_stream_dropped = reg.counter(
            "mlt_engine_stream_dropped_events_total",
            help="incremental stream events shed because a consumer "
                 "fell behind its bounded emission queue")
        # cross-replica KV handoff (ISSUE 19, serving/handoff/): pages
        # and wire bytes this engine exported (prefill role) / imported
        # (decode role, /admin/kv_push)
        self._m_kv_export_pages = reg.counter(
            "mlt_engine_kv_export_pages_total",
            help="KV pool pages exported for cross-replica handoff")
        self._m_kv_export_bytes = reg.counter(
            "mlt_engine_kv_export_bytes_total",
            help="wire bytes of exported KV handoff blobs")
        self._m_kv_import_pages = reg.counter(
            "mlt_engine_kv_import_pages_total",
            help="KV pool pages installed from pushed handoff blobs "
                 "(deduped pages excluded)")
        self._m_kv_import_bytes = reg.counter(
            "mlt_engine_kv_import_bytes_total",
            help="wire bytes of imported KV handoff blobs")
        # speculative-decoding instruments, registered only when the spec
        # path can run (mlt_engine_spec_* stays absent from scrapes of
        # non-speculating engines)
        self._m_spec_draft = self._m_spec_accepted = None
        self._m_spec_ratio = self._m_spec_len = None
        if self.spec_k:
            self._m_spec_draft = reg.counter(
                "mlt_engine_spec_draft_tokens_total",
                help="draft tokens proposed to the verifier")
            self._m_spec_accepted = reg.counter(
                "mlt_engine_spec_accepted_tokens_total",
                help="draft tokens the target model accepted")
            self._m_spec_ratio = reg.histogram(
                "mlt_engine_spec_acceptance_ratio",
                help="per-slot-tick accepted/drafted fraction",
                buckets=[0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                         0.875, 1.0])
            self._m_spec_len = reg.histogram(
                "mlt_engine_spec_accepted_length",
                help="tokens emitted per slot per speculative tick",
                buckets=[float(i) for i in range(1, self.spec_k + 2)])
            reg.gauge("mlt_engine_spec_k",
                      help="speculation depth cap (--spec_k)"
                      ).set(self.spec_k)
        reg.gauge("mlt_engine_sched_policy_info",
                  help="active scheduling policy (value always 1)",
                  labels={"policy": self.policy.name}).set(1)
        reg.gauge("mlt_engine_max_slots",
                  help="decode slots in the tick program").set(self.max_slots)
        reg.gauge("mlt_engine_pool_pages",
                  help="allocatable KV pool pages (null page excluded)"
                  ).set(self.pool.num_pages - 1)
        # quantized-KV capacity telemetry (ISSUE 13): the byte budget the
        # pool occupies (values, target + draft) and the per-page scale
        # overhead, so capacity dashboards and the router can reason in
        # bytes; the kv_dtype info gauge names the storage mode
        reg.gauge("mlt_engine_kv_pool_bytes",
                  help="device bytes of KV value storage (target + draft)"
                  ).set(self.pool.kv_pool_bytes())
        reg.gauge("mlt_engine_kv_scale_bytes",
                  help="device bytes of per-page quantization scales "
                       "(0 for bf16)").set(self.pool.kv_scale_bytes())
        reg.gauge("mlt_engine_kv_dtype_info",
                  help="KV storage mode (value always 1)",
                  labels={"kv_dtype": self.kv_dtype}).set(1)
        # pipeline-parallel serving telemetry (ISSUE 20): stage count of
        # the compiled tick (1 = flat TP-only engine) and the per-stage
        # slice of the pool byte budget — the number a pp replica's HBM
        # actually holds (the servable-model-size multiplier)
        reg.gauge("mlt_engine_pp_stages",
                  help="pipeline stages in the serving tick "
                       "(pp mesh axis; 1 = unpipelined)").set(self._pp)
        reg.gauge("mlt_engine_kv_stage_bytes",
                  help="per-stage device bytes of KV value storage "
                       "(kv_pool_bytes / pp)"
                  ).set(self.pool.kv_stage_bytes())
        if mesh is not None:
            for ax, size in dict(mesh.shape).items():
                reg.gauge("mlt_mesh_axis_size", help="mesh axis size",
                          labels={"axis": str(ax)}).set(size)
        # compute/collective overlap telemetry (ISSUE 15): which overlap
        # mode this engine's compiled programs were built with — asserted
        # by the /metrics scrape test and the bench_tp overlap arm
        reg.gauge("mlt_tp_overlap_info",
                  help="TP compute/collective overlap mode of the "
                       "compiled forward (value always 1)",
                  labels={"mode": self._overlap_mode,
                          "tp": str(self._tp)}).set(1)

    def _asarray(self, x):
        """Host -> device for tick/prefill operands: mesh-replicated when a
        mesh is active (slot vectors, block tables, token rows are identical
        on every shard), committed to the parameters' device where they
        are, plain asarray otherwise."""
        if self._repl is None:
            return jnp.asarray(x)
        # one transfer: an upload and then a placement is twice the host's
        # work a tick (plan_upload_ms.batch 4.2 -> 5.2 ms, PERF.md, PR 64)
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return jax.device_put(x, self._repl)

    def _overlap_span(self):
        """Tracer span marking an overlapped forward dispatch
        (``forward-tp{N}-overlap`` — the observable the ISSUE 15
        acceptance asserts in trace dumps); a no-op context when overlap
        is off, so plain engines emit nothing new."""
        import contextlib

        if self._overlap is None or not self._overlap.ring_rows:
            return contextlib.nullcontext()
        from megatron_llm_tpu.parallel.overlap import overlap_scope_name

        return obs_trace.span(overlap_scope_name(self._tp), mode="ring",
                              tp=self._tp)

    def _pp_span(self):
        """Tracer span marking a pipeline-parallel tick dispatch
        (``engine-pp-tick`` with pp/stages/tp attrs — the observable the
        ISSUE 20 satellite asserts in trace dumps); a no-op context on
        flat engines, so pp=1 dispatch emits nothing new."""
        import contextlib

        if self._pp <= 1:
            return contextlib.nullcontext()
        return obs_trace.span("engine-pp-tick", pp=self._pp,
                              stages=self._pp, tp=self._tp)

    @property
    def _class_statics(self) -> Tuple:
        """Nothing for a uniform model (its programs' keys stay as they
        were); a patterned model's window class's geometry."""
        if self.spool not in (None, self.pool):
            return ("state_class", self.spool.num_pages,
                    self.spool.kv_statics)
        if self.wpool is None:
            return ()
        return ("window_class", self.wpool.num_pages, self._window)

    @property
    def _kv(self):
        """What a tick program takes and returns as ``pool_kv``: the
        pool's leaf, or for a patterned model the (full, window) pair."""
        if len(self._pools) == 1:
            return self.pool.kv
        return tuple(pl.kv for pl in self._pools)

    @_kv.setter
    def _kv(self, kv) -> None:
        if len(self._pools) == 1:
            self.pool.kv = kv
        else:
            for pl, leaf in zip(self._pools, kv):
                pl.kv = leaf

    @property
    def _mesh_statics(self) -> Tuple:
        """Compiled-program cache key extension: engines on different mesh
        layouts must not share executables (gen.cached_jit is process-wide).
        The EFFECTIVE overlap modes ride in the key too — an overlap (or
        vocab-ring) engine's ring programs and a plain engine's GSPMD
        programs have identical signatures, and the fingerprint alone
        cannot separate engines whose cfg matches but whose mesh makes the
        flag inert.  pp geometry needs no extra component: build_mesh
        always materializes the pp axis, so a pp=2 engine's shape tuple
        (("cp",1),("dp",1),("ep",1),("pp",2),("tp",1)) already diverges
        from every flat engine's — pinned by tests/test_pp_serve.py."""
        if self.mesh is None:
            return ("mesh", None, "vocab_ring", "off", "tp_overlap", "off")
        return ("mesh", tuple(sorted(dict(self.mesh.shape).items())),
                "vocab_ring", "ring" if self._vocab_ring else "off",
                "tp_overlap", self._overlap_mode)

    # -- compiled programs -------------------------------------------------

    def _ragged_tick(self, pre_rows: int):
        """THE tick (generation/ragged.py): decode slots,
        verify blocks and ``pre_rows`` prefill-chunk rows in ONE compiled
        launch.  Every piece of tick composition — which slots decode,
        per-slot speculation depth, which prompt positions prefill, their
        block tables and kv horizons — is a traced operand (never a
        static; graftcheck's recompile-hazard rule flags ragged metadata
        that strays into the statics key).  The ONLY shape is
        ``pre_rows``, the live prefill-row count bucketed to
        ``prefill_chunk`` multiples: a BOUNDED set of at most
        ``1 + prefill_rows // prefill_chunk`` executables (0 rows = the
        pure decode/verify tick), so a decode-heavy tick never pays for
        dead prefill rows and tick composition changes re-dispatch, never
        recompile
        (tests/test_ragged_tick.py pins the bound)."""
        fn = self._ragged_fns.get(pre_rows)
        if fn is not None:
            return fn
        from megatron_llm_tpu.generation.ragged import make_ragged_tick_fn

        if self.spec_k:
            statics = ("engine_ragged_tick", self.max_slots,
                       self.pages_per_seq, self.page_size,
                       self.pool.num_pages, self.pool.kv_statics,
                       self.spec_k, pre_rows, self._pre_tables_cap,
                       gen.config_fingerprint(self.draft_cfg),
                       self.pool.draft_kv_statics, self._mesh_statics)
            fn = gen.cached_jit(
                self.cfg, "engine_ragged_tick", statics,
                lambda: make_ragged_tick_fn(
                    self.cfg, self.draft_cfg, self.spec_k,
                    pre_rows, tp=self._tp, mesh=self.mesh),
                donate_argnums=(2, 3))
        else:
            statics = ("engine_ragged_tick", self.max_slots,
                       self.pages_per_seq, self.page_size,
                       self.pool.num_pages, self.pool.kv_statics,
                       0, pre_rows, self._pre_tables_cap,
                       self._mesh_statics) + self._class_statics
            fn = gen.cached_jit(
                self.cfg, "engine_ragged_tick", statics,
                lambda: make_ragged_tick_fn(
                    self.cfg, None, 0, pre_rows, tp=self._tp,
                    mesh=self.mesh),
                donate_argnums=(1,))
        self._ragged_fns[pre_rows] = fn
        return fn

    # A program's first call traces, lowers and compiles (or loads) it on
    # the scheduler thread while every open stream waits: a ``tick-program``
    # start-up phase around that call alone, so a tick whose program exists
    # pays one dictionary lookup.

    def _tick_program(self, pre_rows: int):
        new = pre_rows not in self._ragged_fns
        fn = self._ragged_tick(pre_rows)
        return obs_compiles.startup_phase(
            "tick-program", rows=pre_rows)(fn) if new else fn

    def _chunk_program(self, rows: int, kv_pages: int):
        new = (rows, kv_pages) not in self._chunk_fns
        fn = self._score_chunk(rows, kv_pages)
        return obs_compiles.startup_phase(
            "tick-program", rows=rows, kv_pages=kv_pages)(fn) if new else fn

    def _score_chunk(self, rows: int, kv_pages: int):
        """One teacher-forced prefill CHUNK of a ``return_log_probs``
        prompt: feed ``rows`` prompt tokens at positions
        ``start..start+rows-1`` through the block table (write K/V into the
        owned pages, attend over the first ``kv_pages`` pages) and return
        each position's log-prob of its target token.  Compiled per (rows,
        page horizon) — both page-aligned and horizon bucketed, so a
        server sees a handful of shapes."""
        key = (rows, kv_pages)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        draft_cfg = self.draft_cfg
        from megatron_llm_tpu.parallel import overlap as tp_overlap_mod
        from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

        ovl = self._overlap
        ppc = self._ppc

        def chunk(params, tokens, start, bt, pool_kv, targets):
            with tp_overlap_mod.activate(ovl), pp_serve_mod.activate(ppc):
                out, pool_kv = model_forward(
                    cfg, params, tokens,
                    position_ids=start[:, None] + jnp.arange(rows)[None, :],
                    rope_cache=make_rope_cache(cfg),
                    kv_caches=pool_kv,
                    paged=PagedState(bt, start),
                    logits_postprocess=True,
                )
            lp = gen._gather_token_log_probs(out, targets)
            return pool_kv, lp[0]

        def chunk_spec(params, draft_params, tokens, start, bt,
                       pool_kv, draft_kv, targets):
            # target chunk plus the DRAFT model's chunk through the same
            # block table: a speculating engine keeps both caches filled
            # for every prefilled page, so trie-matched pages (prefix hits,
            # preemption resume) carry valid draft K/V too
            pool_kv, lp = chunk(params, tokens, start, bt, pool_kv, targets)
            with tp_overlap_mod.activate(ovl), pp_serve_mod.activate(ppc):
                _, draft_kv = model_forward(
                    draft_cfg, draft_params, tokens,
                    position_ids=start[:, None] + jnp.arange(rows)[None, :],
                    rope_cache=make_rope_cache(draft_cfg),
                    kv_caches=draft_kv,
                    paged=PagedState(bt, start),
                    logits_postprocess=False,
                )
            return pool_kv, draft_kv, lp

        # the True is the key's log-prob flag from when the program had a
        # variant without scores: kept so the compile cache's entries hold
        statics = ("engine_prefill_chunk", rows, kv_pages, True,
                   self.page_size, self.pool.num_pages,
                   self.pool.kv_statics, self._mesh_statics)
        if self.spec_k:
            statics += ("spec", gen.config_fingerprint(draft_cfg))
            fn = gen.cached_jit(self.cfg, "engine_prefill_chunk", statics,
                                lambda: chunk_spec,
                                donate_argnums=(5, 6))
        else:
            fn = gen.cached_jit(self.cfg, "engine_prefill_chunk", statics,
                                lambda: chunk, donate_argnums=(4,))
        self._chunk_fns[key] = fn
        return fn

    def _copy_page(self):
        """Device page copy for copy-on-write (src/dst are traced scalars —
        one compile serves every copy)."""
        if self._copy_fn is not None:
            return self._copy_fn

        def copy(pool_kv, src, dst):
            # a page id is the same page in every layer, whatever the row:
            # tree-mapped so quantized pools clone the page's scale row
            # together with its values — a COW page is byte-identical
            # to its source in BOTH leaves, so the refeed rewrite sees
            # exactly the shared page's quantization state
            return jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), pool_kv)

        def copy_spec(pool_kv, draft_kv, src, dst):
            # COW must clone the page in BOTH caches: the refeed tick
            # rewrites the draft K/V at the same position too
            return copy(pool_kv, src, dst), copy(draft_kv, src, dst)

        statics = ("engine_copy_page", self.pool.num_pages, self.page_size,
                   self.pool.kv_statics, self._mesh_statics)
        if self.spec_k:
            statics += ("spec", gen.config_fingerprint(self.draft_cfg))
            self._copy_fn = gen.cached_jit(
                self.cfg, "engine_copy_page", statics, lambda: copy_spec,
                donate_argnums=(0, 1))
        else:
            self._copy_fn = gen.cached_jit(
                self.cfg, "engine_copy_page", statics, lambda: copy,
                donate_argnums=(0,))
        return self._copy_fn

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               **kw) -> EngineRequest:
        """Enqueue a generation; returns the request future.

        Raises :class:`gen.InvalidRequest` (a ValueError) for requests
        that can never fit (the legacy engine's request-size guard,
        generation/api._check_limits) and :class:`EngineOverloaded` when
        the queue is at capacity."""
        prompt = [int(t) for t in prompt]
        if len(prompt) < 1:
            raise gen.InvalidRequest("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise gen.InvalidRequest("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.max_seq:
            raise gen.InvalidRequest(
                "Length of prompt + tokens_to_generate longer than allowed")
        if kw.get("return_log_probs"):
            try:
                refuse_unserved(self.cfg, log_probs=True)
            except ValueError as e:
                raise gen.InvalidRequest(str(e)) from None
        req = self._new_request(prompt, max_new_tokens, kw)
        req._t_submit = time.monotonic()
        # flight record + enqueue event (observability/flight.py): a
        # request turned away at the door still leaves a record, so an
        # overload burst is reconstructable from /debug/requests
        req._flight = self.flight.open(
            req.trace_id, prompt_tokens=len(prompt),
            max_new_tokens=max_new_tokens, priority=req.priority,
            t_submit=req._t_submit)
        with obs_trace.span("engine-enqueue", prompt_len=len(prompt),
                            trace_id=req.trace_id):
            with self._work:
                if self.max_queue and len(self._queue) >= self.max_queue:
                    req._flight.finish("overload",
                                       queued=len(self._queue))
                    self.flight.close(req._flight)
                    raise EngineOverloaded(
                        f"request queue full ({self.max_queue} waiting)",
                        retry_after=self._drain_eta(len(self._queue)),
                        info=self._overload_info())
                quota = self._quota.get(req.priority)
                if quota is not None:
                    depth = sum(1 for r in self._queue
                                if r.priority == req.priority)
                    if depth >= quota:
                        req._flight.finish("overload", queued=depth,
                                           quota=quota)
                        self.flight.close(req._flight)
                        raise EngineOverloaded(
                            f"priority-{req.priority} queue full "
                            f"({quota} waiting)",
                            retry_after=self._drain_eta(depth),
                            info=self._overload_info())
                self._seqno += 1
                req._seqno = self._seqno
                self._queue.append(req)
                req._flight.event("enqueue", queued=len(self._queue))
                if req._stream is not None:
                    self._stream_subs += 1
                    if obs_registry.publishing():
                        self._m_stream_subs.set(self._stream_subs)
                if obs_registry.publishing():
                    self._m_requests.inc()
                self._publish_queued_locked()
                self._work.notify()
        return req

    def submit_stream(self, prompt: Sequence[int], max_new_tokens: int,
                      *, stream_events: int = 256, **kw):
        """Enqueue a generation with a live token stream attached.

        Returns ``(req, queue)`` — the same :class:`EngineRequest` future
        ``submit`` returns plus the :class:`StreamQueue
        <megatron_llm_tpu.serving.streaming.StreamQueue>` the apply paths
        feed under the engine lock: one ``token`` event per applied batch
        (a verify tick retires several tokens per flush), then exactly
        one terminal ``done``/``error`` event carrying the flight-record
        timing payload.  ``stream_events`` bounds the queue; a consumer
        that falls behind sheds incremental events (counted in
        ``mlt_engine_stream_dropped_events_total`` and in the terminal
        event's ``dropped_events``) but always gets the terminal —
        drop-to-terminal, never backpressure into the tick loop."""
        from megatron_llm_tpu.serving.streaming import StreamQueue

        q = StreamQueue(maxsize=stream_events)
        req = self.submit(prompt, max_new_tokens, _stream=q, **kw)
        return req, q

    def _stream_emit_locked(self, req: EngineRequest, tokens,
                            log_probs) -> None:  # holds _lock
        """Publish one incremental token batch to the request's stream
        (no-op for plain submits).  The queue is a leaf lock and the
        publish never blocks — the committed lock-order edge
        ContinuousBatchingEngine._lock -> StreamQueue._lock mirrors the
        engine→FlightRecorder discipline."""
        q = req._stream
        if q is None or not tokens:
            return
        shed = q.publish_tokens(tokens, log_probs)
        if not shed:
            self._stream_dirty = True
        elif obs_registry.publishing():
            self._m_stream_dropped.inc(shed)

    def _streams_dirty_locked(self) -> bool:  # holds _lock
        """Whether the stream writer is owed a kick for tokens an apply
        put into stream queues (the debt is the caller's from here: it
        kicks after leaving the lock)."""
        dirty, self._stream_dirty = self._stream_dirty, False
        return dirty

    def _kick_streams(self) -> None:
        """Tell the stream writer, ONCE for an applied tick, that stream
        queues hold new tokens.  Outside ``_lock``; an ``Event.set``.

        Called where the scheduler thread is about to wait for the device
        (the fetch of the NEXT tick), not at the end of the apply: the
        writer's pass is a hundred short sends, each of which gives the
        interpreter up and asks for it back, and beside admit, plan and
        launch each of those hand-overs is the scheduler's time (PERF.md
        section 6, PR 34: 1.7 ms a tick at 100 streams); beside the fetch
        the scheduler does not want the interpreter.  A frame so leaves up
        to one host cycle after its tick was applied; a stream's first
        and last frames are its handler's and wait for nothing."""
        kick = self.stream_kick
        if kick is not None:
            kick()

    def _stream_finish_locked(self, req: EngineRequest, kind: str,
                              **data) -> None:  # holds _lock
        """Publish the terminal stream event and detach the queue (a
        request reaches exactly one of _retire/_fail_locked/_shed_locked,
        but detaching keeps a double finish structurally impossible)."""
        q = req._stream
        if q is None:
            return
        req._stream = None
        self._stream_subs -= 1
        if obs_registry.publishing():
            self._m_stream_subs.set(self._stream_subs)
        from megatron_llm_tpu.serving.streaming import StreamEvent

        q.publish_terminal(StreamEvent(kind, data=data))

    def _drain_eta(self, depth: int) -> float:  # holds _lock
        """Seconds until ``depth`` queued requests likely drain — the
        EMA retirement interval (tick EMA before any retirement), clamped
        to [1, 60].  This is the Retry-After a 503 carries, so it tracks
        load instead of being a constant."""
        per = (self._ema_retire_s if self._ema_retire_s is not None
               else self._ema_tick_s)
        if per is None:
            return 1.0
        return min(60.0, max(1.0, depth * per))

    def _overload_info(self) -> dict:  # holds _lock
        return {"queued": len(self._queue), "policy": self.policy.name,
                "active_slots": sum(r is not None for r in self._slots)}

    def _note_ttft_locked(self, ttft_s: float) -> None:  # holds _lock
        """Feed the real first-token EMA (published in /health; the
        router's slo_aware wait predictions consume it)."""
        self._ema_ttft_s = (ttft_s if self._ema_ttft_s is None
                            else 0.7 * self._ema_ttft_s + 0.3 * ttft_s)

    def _publish_queued_locked(self, force: bool = False) -> None:  # holds _lock
        """THE queue-depth gauge update point (total + per-priority
        labels) — every enqueue/admit/preempt/shed path funnels here, so
        the gauges can never disagree with each other.  ``force`` is the
        scrape-time pull (server metrics_text), which refreshes even with
        per-tick publishing switched off."""
        if not (force or obs_registry.publishing()):
            return
        self._m_queued.set(len(self._queue))
        by_prio: Dict[int, int] = {}
        for r in self._queue:
            by_prio[r.priority] = by_prio.get(r.priority, 0) + 1
        self._queued_prios |= set(by_prio)
        reg = obs_registry.get_registry()
        for prio in self._queued_prios:  # stale labels drop to 0
            reg.gauge("mlt_engine_queued_requests",
                      help="requests awaiting a slot",
                      labels={"priority": str(prio)}
                      ).set(by_prio.get(prio, 0))

    def _fill_end(self, prompt_len: int) -> int:
        """Where prefill stops.  Pages: the prompt bucketed up to whole
        pages (the padding's keys are never attended, and the first decode
        row writes the last token's again, the same bits).  A state cannot
        take a token twice: prefill ends BEFORE the last token, which the
        first decode row feeds."""
        if self.state:
            return prompt_len - 1
        return _bucket_up(prompt_len, self.page_size)

    def _sched_state(self, now: float) -> SchedulerState:  # holds _lock
        """Read-only snapshot for policy decisions (under _lock)."""
        return SchedulerState(
            now=now,
            ema_tick_s=self._ema_tick_s,
            ema_retire_s=self._ema_retire_s,
            free_slots=sum(r is None for r in self._slots),
            queue_depth=len(self._queue),
            prefill_chunk=self.prefill_chunk,
            ttft_ema_s=self._ema_ttft_s,
        )

    def _admit(self) -> None:
        """Move queued requests into slots while the policy and pages
        allow.

        The policy owns the DECISIONS: which queued request to try next
        (``admission_order``; fcfs = queue head with nothing skipping it,
        ``barrier_admission``), which queued requests to shed outright,
        and which decoding victim to preempt when the best candidate
        can't get a slot or its page budget.  The MECHANISMS are the
        classes' (generation/pools.py).  Planning (trie match, budget
        check, allocation, slot assignment) happens under ``_lock``; only
        the device work (the COW copy) runs outside it, with every owned
        page ref tracked in ``req._mem`` throughout so a failure path
        releases exactly what is held."""
        while True:
            with self._lock:
                if not self._queue:
                    return
                now = time.monotonic()
                state = self._sched_state(now)
                shed = self.policy.shed(list(self._queue), state)
                for victim, reason in shed:
                    if victim in self._queue:  # defensive vs policy bugs
                        self._queue.remove(victim)
                        self._shed_locked(victim, reason)
                if shed:
                    self._publish_queued_locked()
                    if not self._queue:
                        return
                order = self.policy.admission_order(list(self._queue),
                                                    state)
                req = cow = None
                try:
                    slot = self._slots.index(None)
                except ValueError:
                    slot = None
                if slot is not None:
                    for cand in order:
                        cow = self._plan_chunked(cand, slot)
                        if cow is not None:
                            req = cand
                            break
                        if self.policy.barrier_admission:
                            break  # page pressure: head waits, no skips
                if req is None:
                    # blocked on a slot or on pages: the policy may evict
                    # the lowest-value decoding request — its pages go
                    # back to the pool (prefix-covered ones stay in the
                    # trie) and it re-queues for a cached-page resume
                    victim = None
                    if order:
                        decoding = [r for r in self._slots
                                    if r is not None
                                    and r._phase == "decode"
                                    and not r.return_log_probs]
                        victim = self.policy.preempt_victim(
                            order[0], decoding, state)
                    if victim is None:
                        return
                    self._preempt_locked(victim)
                    continue
                self._queue.remove(req)
                self._publish_queued_locked()
            try:
                self._place_chunked(req, cow)
            except Exception as e:  # noqa: BLE001 — surface to the waiter
                self._fail(req, e)

    def _preempt_locked(self, victim: EngineRequest) -> None:  # holds _lock
        """Preemption by page release: park the victim's finished KV
        pages in the prefix-cache trie, release every page it holds
        (trie-registered ones go cached-idle, the rest go free), return
        its unused worst-case commitment, and re-queue it.  On
        re-admission the trie match re-takes the SAME physical pages, so
        resume recomputes only the partial last page — bitwise identical
        to never having been preempted (tests/test_scheduler.py)."""
        assert victim._phase == "decode" and victim._slot >= 0
        slot = victim._slot
        seq = victim.seq_tokens
        # every page fully covered by seq[:-1] is finished K/V the
        # resume's refeed tick will never write — safe to share
        self._cache_insert_locked(victim, seq,
                                  self._parkable_pages(victim, seq))
        self._clear_slot_locked(slot)
        pages = self._release_pages_locked(victim)
        victim._phase = "queued"
        victim._slot = -1
        victim._fill_pos = 0
        victim._preemptions += 1
        victim._flight.note_preemption()
        victim._flight.set_phase("preempted", step=victim._step,
                                 pages_released=pages)
        self.preemptions += 1
        self._queue.append(victim)  # position is policy-ordered anyway
        if obs_registry.publishing():
            self._m_preempt.inc()
        self._publish_queued_locked()
        self._dirty = True

    def _shed_locked(self, req: EngineRequest,
                     reason: str) -> None:  # holds _lock
        """Drop a QUEUED request (owns no pages): fail its future with a
        retryable :class:`RequestShed` carrying the drain estimate."""
        req.shed = True
        req.shed_retry_after = self._drain_eta(len(self._queue))
        req._phase = "finished"
        req.error = f"request shed: {reason}"
        req.finished = True
        req._flight.finish("shed", reason=reason)
        self.flight.close(req._flight)
        self._stream_finish_locked(req, "error", error=req.error, shed=True,
                                   retry_after=req.shed_retry_after)
        self.shed_requests += 1
        if obs_registry.publishing():
            self._m_shed.inc()
        req._done.set()

    def preempt(self, req: EngineRequest) -> bool:
        """Force-preempt one decoding request (ops/test hook — policy-
        driven preemption runs the same ``_preempt_locked`` path during
        admission).  False if the request isn't currently decoding."""
        with self._lock:
            if req._phase != "decode":
                return False
            self._preempt_locked(req)
            return True

    def scheduler_stats(self) -> dict:
        """Control-plane snapshot for ``/health`` (generation/server.py)
        and the slo bench."""
        with self._lock:
            by_prio: Dict[str, int] = {}
            for r in self._queue:
                k = str(r.priority)
                by_prio[k] = by_prio.get(k, 0) + 1
            return {
                "policy": self.policy.name,
                "queued": len(self._queue),
                "queued_by_priority": by_prio,
                "preemptions": self.preemptions,
                "shed": self.shed_requests,
                "deadline_misses": self.deadline_misses,
                "ema_tick_ms": (None if self._ema_tick_s is None
                                else round(self._ema_tick_s * 1e3, 3)),
                "ema_retire_ms": (None if self._ema_retire_s is None
                                  else round(self._ema_retire_s * 1e3, 3)),
                # measured submit-to-first-token EMA (ISSUE 12): the
                # honest TTFT signal the router's wait predictions use
                "ttft_ema_ms": (None if self._ema_ttft_s is None
                                else round(self._ema_ttft_s * 1e3, 3)),
                "retry_after_s": round(self._drain_eta(len(self._queue)), 3),
            }

    # ---- admission ----

    def _plan_chunked(self, req: EngineRequest,
                      slot: int) -> Optional[bool]:  # holds _lock
        """Under _lock: match the prefix cache, ask every class for its
        pages (a grant that one class refuses is refused whole), and
        reserve the slot.  None = can't admit now (matched refs undone);
        else whether the first tick would write a shared page, which
        :meth:`_place_chunked` copies first.  Works on the request's
        EFFECTIVE prompt (prompt + generated): a preempted request
        re-admits here and its parked pages match straight back out of
        the trie."""
        ps = self.page_size
        seq = req.seq_tokens
        prompt_len = len(seq)
        # pages its tokens fill at most (a class caps that at its own)
        max_total = -(-min(len(req.prompt) + req.max_new_tokens,
                           self.max_seq) // ps)
        lists: List[List[int]] = [[] for _ in self._classes]
        if self.cache is not None and not req.return_log_probs:
            # log-prob requests recompute the whole prompt (the teacher-
            # forced scores need every position's logits), so they take no
            # shared pages — their pages still feed the cache afterwards
            lists = self.cache.match_lists(seq, prompt_len // ps)
        matched = lists[0]
        covered = len(matched) * ps
        # full page-aligned match: the first tick re-feeds the last prompt
        # token and would WRITE the final shared page -> copy-on-write
        cow = bool(matched) and covered == prompt_len and not self._blocks
        n_keep = len(matched) - (1 if cow else 0)
        fill_end = self._fill_end(prompt_len)
        suffix_pages = -(-(fill_end - covered) // ps)
        held_core = n_keep + (1 if cow else 0) + suffix_pages
        extra = self._first_pages(max_total - held_core)  # decode pages
        # each class grants of these what is its to grant now and books
        # the rest (``ClassMemory.demand``), all of them or none
        want = (n_keep, int(cow), suffix_pages + extra, max_total)
        if not all(cls.can_admit(*want) for cls in self._classes):
            for cls, got in zip(self._classes, lists):
                cls.undo(got)
            return None
        # every ref this request owns lives in _mem from here on, so any
        # failure path releases exactly the right set; the COW page swap
        # reorders the lists after the device copy lands
        req._mem = [cls.admit(got, *want)
                    for cls, got in zip(self._classes, lists)]
        req._fill_pos = prompt_len if cow else covered
        req._hit_tokens = covered
        req._slot = slot
        self._slots[slot] = req
        self.prefix_hit_tokens += covered
        self.prefix_miss_tokens += prompt_len - covered
        if self.state and req._preemptions:
            # the preemption dropped the state: its tokens go through
            # prefill again (a page pool takes them back out of the trie)
            self.state_recomputed_tokens += fill_end
            if obs_registry.publishing():
                self._m_state["recomputed_tokens"].inc(fill_end)
        req._flight.note_hit_tokens(covered)
        req._flight.set_phase(
            "prefill", kind="resume" if req._preemptions else "admit",
            slot=slot, hit_tokens=covered, pages=len(req._mem[0].pages))
        if obs_registry.publishing():
            self._m_hit_tokens.inc(covered)
            self._m_miss_tokens.inc(prompt_len - covered)
        return cow

    def _place_chunked(self, req: EngineRequest, cow: bool) -> None:
        if cow:
            # device copy OUTSIDE the lock (driver thread; serialized with
            # ticks via _drive_lock), a class at a time: the shared page at
            # ``keep`` into the request's own behind it; then drop our ref
            for pl, mem in zip(self._pools, req._mem):
                src = self._asarray(np.int32(mem.pages[mem.keep]))
                dst = self._asarray(np.int32(mem.pages[mem.keep + 1]))
                if self.spec_k:
                    pl.kv, pl.draft_kv = self._copy_page()(
                        pl.kv, pl.draft_kv, src, dst)
                else:
                    pl.kv = self._copy_page()(pl.kv, src, dst)
        with self._lock:
            if cow:
                # block-table order: kept shared pages, the private COW
                # copy, then the first decode page
                for cls, mem in zip(self._classes, req._mem):
                    cls.drop_shared(mem)
                self.cow_copies += 1
                if obs_registry.publishing():
                    self._m_cow.inc()
            if req._fill_pos >= self._fill_end(len(req.seq_tokens)):
                # fully served from cache (or a state's one-token prompt:
                # nothing to prefill): straight to decode (or, for
                # a prefill_only request, straight to handoff)
                self._activate_or_handoff(req, req._slot)
            else:
                req._phase = "prefill"
                self._prefill_q.append(req)

    def _cache_insert_locked(self, req, seq, n_pages: int):  # holds _lock
        """The first ``n_pages`` whole pages of ``seq`` go to the trie."""
        if self.cache is not None:
            self.cache.insert_lists(seq, [m.pages for m in req._mem], n_pages)

    # ---- shared lifecycle tail ----

    def _activate(self, req: EngineRequest,
                  slot: int) -> None:  # holds _lock
        """Under _lock: install the slot's decode state (effective prompt
        fully in pages); the next tick samples the next token by
        re-feeding the last token at position len(seq) - 1 — identical
        K/V rewrite into a PRIVATE page (COW guarantees it).  A resumed
        request re-enters with its ORIGINAL key and step count, so its
        sampling stream continues exactly where preemption cut it."""
        seq = req.seq_tokens
        if req._key is None:
            seed = req.seed
            if seed is None:
                seed = int.from_bytes(os.urandom(4), "little")
            req._key = _request_key(seed)
        self._slide_locked(req, len(seq) - 1)
        for cls, mem in zip(self._classes, req._mem):
            cls.install(slot, mem)
        self._positions[slot] = len(seq) - 1
        self._tokens[slot] = seq[-1]
        self._temperature[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        self._keys[slot] = req._key
        self._steps[slot] = req._step
        req._phase = "decode"
        req._flight.set_phase("decode", pos=len(seq) - 1)
        self._dirty = True

    def _activate_or_handoff(self, req: EngineRequest,
                             slot: int) -> None:  # holds _lock
        """Prefill-completion dispatch: normal requests activate into
        decode; ``prefill_only`` requests (disaggregated serving, ISSUE
        19) park for export instead — they never take a decode tick."""
        if req.prefill_only:
            self._handoff_ready_locked(req, slot)
        else:
            self._activate(req, slot)

    def _handoff_ready_locked(self, req: EngineRequest,
                              slot: int) -> None:  # holds _lock
        """Prefill finished for a ``prefill_only`` request: free the
        slot (the scheduler is done with it), KEEP the page refs (the
        export must read stable bytes), return the never-needed decode
        commitment, and wake the exporter waiting on ``_done``.  The
        flight record enters the ``handoff`` phase bucket here, so the
        migrated request's latency decomposition still provably sums
        (PR 12 invariant across the hop)."""
        self._clear_slot_locked(slot)
        # a handoff request never decodes: its worst-case decode-page
        # commitment returns to the ledger now
        for cls, mem in zip(self._classes, req._mem):
            cls.trim(mem)
        req._slot = -1
        req._phase = "handoff"
        req._flight.set_phase("handoff", pages=len(req._mem[0].pages))
        req._done.set()

    def _finish_handoff_locked(self, req: EngineRequest,
                               **args) -> None:  # holds _lock
        """Retire a handoff-phase request after (attempted) export:
        release every held page — trie-registered prompt pages go
        cached-idle, exactly like a preemption park, so a later local
        request (or a second export) still hits them."""
        if req._phase != "handoff":
            return  # failed/shed earlier; _fail/_shed already cleaned up
        self._release_pages_locked(req)
        req._phase = "finished"
        req.finished = True
        req._t_done = time.monotonic()
        req._flight.finish("handoff", **args)
        self.flight.close(req._flight)
        req._done.set()

    def _clear_slot_locked(self, slot: int) -> None:  # holds _lock
        """An emptied slot: a dead row (null tables, greedy, position 0)."""
        self._slots[slot] = None
        for cls in self._classes:
            cls.clear(slot)
        self._positions[slot] = 0
        self._tokens[slot] = 0
        self._top_k[slot] = 1
        self._top_p[slot] = 0.0
        self._temperature[slot] = 1.0
        self._dirty = True

    def _release_pages_locked(self, req: EngineRequest) -> int:  # holds _lock
        """Give back every page ``req`` holds, in each class, and what the
        ledgers still held for it; returns how many pages those were."""
        return sum(cls.release(mem)
                   for cls, mem in zip(self._classes, req._mem))

    def _slide_locked(self, req: EngineRequest,
                      qpos: int) -> int:  # holds _lock
        """Slide ``req``'s windows up to a query at ``qpos`` (nothing for a
        class without one); counts and returns the pages that gave back."""
        gone = sum(cls.slide(mem, qpos)
                   for cls, mem in zip(self._classes, req._mem))
        if gone:
            self.window_pages_released += gone
            if obs_registry.publishing():
                self._m_slid.inc(gone)
        return gone

    def _ledger_violated(self, k: int, whom: str):  # holds _lock
        """A row whose class ``k`` could not grant what its ledger booked."""
        return RuntimeError(
            ("" if k == 0 else f"{self._classes[k].name}-class ")
            + f"KV pool exhausted for {whom} — commitment ledger violated")

    def _fail(self, req: EngineRequest, e: Exception) -> None:
        with self._lock:
            self._fail_locked(req, e)

    def _fail_locked(self, req: EngineRequest,
                     e: Exception) -> None:  # holds _lock
        if 0 <= req._slot < len(self._slots) \
                and self._slots[req._slot] is req:
            self._clear_slot_locked(req._slot)
        self._release_pages_locked(req)
        req._phase = "finished"
        req.error = f"{type(e).__name__}: {e}"
        req.finished = True
        req._flight.finish("error", error=req.error)
        self.flight.close(req._flight)
        self._stream_finish_locked(req, "error", error=req.error)
        req._done.set()

    def _retire(self, slot: int) -> None:  # holds _lock
        req = self._slots[slot]
        self._clear_slot_locked(slot)
        # early termination returns its unneeded worst-case commitment
        self._release_pages_locked(req)
        req._phase = "finished"
        req.finished = True
        # drain-rate EMA (feeds Retry-After + slo shed predictions) and
        # SLO outcome accounting
        now = time.monotonic()
        if self._last_retire_t is not None:
            dt = now - self._last_retire_t
            self._ema_retire_s = (dt if self._ema_retire_s is None
                                  else 0.7 * self._ema_retire_s + 0.3 * dt)
        self._last_retire_t = now
        req._t_done = now
        rec = req._flight
        rec.finish("ok", now=now, tokens=len(req.generated))
        self.flight.close(rec)
        ttft = req.ttft
        if req._stream is not None:
            # terminal stream event: the flight-record timing payload
            # (what the buffered response's "timing" block is built from)
            timing = {"ttft_s": None if ttft is None else round(ttft, 6),
                      "latency_s": round(now - req._t_submit, 6),
                      "tokens": len(req.generated)}
            if rec.enabled:
                timing["decomposition"] = rec.to_dict()["decomposition"]
            self._stream_finish_locked(req, "done", outcome="ok",
                                       timing=timing)
        missed = False
        publishing = obs_registry.publishing()
        if rec.enabled and publishing:
            # honest TTFT/latency decomposition (ISSUE 12): the flight
            # record's phase buckets sum to the measured latency, so
            # these histograms attribute it instead of re-measuring it
            d = rec.to_dict()["decomposition"]
            self._m_queue_wait.observe(d["queue_wait_s"])
            self._m_prefill_compute.observe(d["prefill_s"])
            if req._preemptions:
                self._m_preempted_s.observe(d["preempted_s"])
        if ttft is not None:
            if publishing:
                self._m_ttft.observe(ttft)
            if (req.ttft_deadline_ms is not None
                    and ttft > req.ttft_deadline_ms / 1e3):
                missed = True
                if publishing:
                    self._m_miss_ttft.inc()
                    if rec.enabled:
                        # attribution: blame the phase that ate the
                        # largest TTFT share ({kind}-only stays total)
                        obs_registry.get_registry().counter(
                            "mlt_engine_deadline_miss_total",
                            help="retired requests that missed a "
                                 "declared deadline",
                            labels={"kind": "ttft",
                                    "phase": rec.miss_phase()}).inc()
            if (req.tpot_deadline_ms is not None and req._step > 1
                    and ((now - req._t_first) / (req._step - 1)
                         > req.tpot_deadline_ms / 1e3)):
                missed = True
                if publishing:
                    self._m_miss_tpot.inc()
        if missed:
            self.deadline_misses += 1
        req._done.set()

    def _stopped_by_token(self, req: EngineRequest, tok: int) -> bool:
        if req.stop_on_double_eol:
            prev = (req.generated[-2] if len(req.generated) > 1
                    else req.prompt[-1])
            return tok == gen.GPT2_DOUBLE_EOL or (
                tok == gen.GPT2_EOL and prev == gen.GPT2_EOL)
        if req.stop_on_eol:
            return tok in (gen.GPT2_EOL, gen.GPT2_DOUBLE_EOL)
        if not req.use_eod_for_termination or req.termination_id is None:
            return False
        return tok == req.termination_id

    # -- speculative decoding ----------------------------------------------

    def _apply_spec_locked(self, active, k_eff, emit_np, lp_np, acc_np,
                           m_np, now) -> int:  # holds _lock
        """Fold one speculative tick's results into the slots: append each
        row's emitted block (truncating at stop tokens / length limits —
        exactly where non-speculative decode would have stopped), advance
        the host mirrors by the KEPT count, update acceptance EMAs and
        spec telemetry, retire finished rows.  Returns tokens emitted
        (the tick's slot-step count for throughput accounting — a spec
        slot reports k-token progress, so SLO/tpot math sees real token
        timestamps, not tick counts)."""
        emitted = 0
        publishing = obs_registry.publishing()
        for i in active:
            req = self._slots[i]
            k_i = int(k_eff[i])
            m_i = int(m_np[i])
            took = 0
            done = False
            for t in range(m_i):
                tok = int(emit_np[i, t])
                req.generated.append(tok)
                req.log_probs.append(float(lp_np[i, t]))
                took += 1
                done = (self._stopped_by_token(req, tok)
                        or len(req.generated) >= req.max_new_tokens
                        or len(req.prompt) + len(req.generated)
                        >= self.max_seq)
                if done:
                    break
            if req._step == 0:
                req._t_first = now
                req._flight.mark_first_token(now)
                self._note_ttft_locked(now - req._t_submit)
            if took:
                self._stream_emit_locked(req, req.generated[-took:],
                                         req.log_probs[-took:])
            req._step += took
            self._positions[i] += took
            self._tokens[i] = int(emit_np[i, took - 1])
            self._steps[i] += took
            emitted += took
            self.spec_emitted_tokens += took
            if k_i > 0:
                a_i = int(acc_np[i])
                self.spec_draft_tokens += k_i
                self.spec_accepted_tokens += a_i
                req._spec_ema = 0.7 * req._spec_ema + 0.3 * (a_i / k_i)
                req._flight.add_spec(k_i, a_i)
                req._flight.event("spec_tick", k=k_i, accepted=a_i,
                                  emitted=took)
                if publishing:
                    self._m_spec_draft.inc(k_i)
                    self._m_spec_accepted.inc(a_i)
                    self._m_spec_ratio.observe(a_i / k_i)
            if publishing:
                self._m_spec_len.observe(took)
            if took != m_i:
                # a stop token cut the block short: the device mirror ran
                # ahead of the kept sequence — force a re-upload
                self._dirty = True
            if done:
                self._retire(i)
        self.spec_ticks += 1
        return emitted

    def spec_stats(self) -> dict:
        """Speculative-decoding snapshot for ``/health`` and the spec
        bench (generation/server.py, bench_decode.py --mode spec)."""
        if not self.spec_k:
            return {"enabled": False}
        with self._lock:
            drafted = self.spec_draft_tokens
            accepted = self.spec_accepted_tokens
            emitted = self.spec_emitted_tokens
            ticks = self.spec_ticks
        return {
            "enabled": True,
            "spec_k": self.spec_k,
            "adaptive": self.spec_adaptive,
            "draft_layers": self.draft_cfg.model.num_layers,
            "draft_tokens": drafted,
            "accepted_tokens": accepted,
            "acceptance_rate": round(accepted / drafted, 4) if drafted else None,
            "emitted_tokens": emitted,
            "tokens_per_tick": round(emitted / ticks, 3) if ticks else None,
        }

    # -- chunked prefill scheduling ---------------------------------------

    def _advance_scored_prefill(self) -> bool:
        """Run ONE scoring chunk for the policy's chosen prefilling
        ``return_log_probs`` request (fcfs: the oldest).  Returns True if
        a chunk ran: one a tick, beside the fused tick.

        The carve-out from the ragged tick: teacher-forced prompt
        log-probs need every chunk position's logits from the s>1 prefill
        program (their bits are pinned by the api scoring contract), so
        ``return_log_probs`` prompts prefill through this chunk program
        while everything else rides the fused tick."""
        with self._lock:
            live = [r for r in self._prefill_q
                    if r._phase == "prefill" and r.return_log_probs]
            if not live:
                return False
            req = self.policy.prefill_order(
                live, self._sched_state(time.monotonic()))[0]
            ps = self.page_size
            chunk = self.prefill_chunk
            seq = req.seq_tokens  # resumed requests re-prefill their tail
            prompt_len = len(seq)
            start = req._fill_pos
            fill_end = _bucket_up(prompt_len, ps)
            # chunk boundaries are ABSOLUTE-position grid multiples of
            # prefill_chunk (first/last chunks may be short): the K/V bits a
            # chunk writes then depend only on (tokens, positions), never on
            # how much prefix the cache covered — the bitwise cache-on/off
            # parity contract
            end = min(fill_end, (start // chunk + 1) * chunk)
            rows = end - start
            # attention horizon: every page the chunk's queries can see,
            # bucketed (multiples of BUCKET tokens) to bound compile count
            kv_pages = min(self.pages_per_seq, _bucket_up(end) // ps)
            tokens = np.zeros((1, rows), np.int32)
            n_real = min(end, prompt_len) - start
            tokens[0, :n_real] = seq[start:start + n_real]
            bt = np.full((1, kv_pages), NULL_PAGE, np.int32)
            pages = req._mem[0].pages
            n_bt = min(len(pages), kv_pages)
            bt[0, :n_bt] = pages[:n_bt]
            targets = np.zeros((1, rows), np.int32)
            n_lp = max(0, min(rows, prompt_len - 1 - start))
            targets[0, :n_lp] = seq[start + 1:start + 1 + n_lp]

        t_chunk = time.monotonic()
        try:
            with obs_trace.span("engine-prefill-chunk", start=start,
                                rows=rows, tp=self._tp,
                                trace_id=req.trace_id):
                if self.spec_k:
                    (self.pool.kv, self.pool.draft_kv,
                     lp) = self._chunk_program(rows, kv_pages)(
                        self.params, self.draft_params,
                        self._asarray(tokens),
                        self._asarray(np.asarray([start], np.int32)),
                        self._asarray(bt), self.pool.kv,
                        self.pool.draft_kv, self._asarray(targets))
                else:
                    self.pool.kv, lp = self._chunk_program(rows, kv_pages)(
                        self.params, self._asarray(tokens),
                        self._asarray(np.asarray([start], np.int32)),
                        self._asarray(bt),
                        self.pool.kv, self._asarray(targets))
            if req.prompt_log_probs is None:
                req.prompt_log_probs = []
            req.prompt_log_probs.extend(
                float(x) for x in np.asarray(lp)[:n_lp])
        except Exception as e:  # noqa: BLE001 — surface to the waiter
            self._fail(req, e)
            return True

        with self._lock:
            req._fill_pos = end
            self.prefill_tokens_computed += rows
            req._flight.event("prefill_chunk", start=start, end=end,
                              rows=rows, fill_end=fill_end)
            req._flight.add_prefill_compute(time.monotonic() - t_chunk)
            if obs_registry.publishing():
                self._m_prefill_tokens.inc(rows)
            if end >= fill_end:
                self._prefill_q.remove(req)
                # cache every page FULLY covered by prompt tokens that
                # the refeed tick will never write: (prompt_len-1)//page
                # excludes the refeed page, so shared pages are
                # immutable from birth
                self._cache_insert_locked(req, seq, (prompt_len - 1) // ps)
                self._activate_or_handoff(req, req._slot)
        return True

    # -- the tick ----------------------------------------------------------

    def step(self) -> int:
        """Admit what fits, advance prefill under the policy's token
        budget, run the tick, and retire finished requests.  Returns the
        number of slots advanced (decode rows ticked, +1 per prefill
        phase that ran; 0 = idle, nothing ran).  Call from one driver at
        a time (:meth:`run_until_idle` / the background loop serialize
        via ``_drive_lock``).

        The whole tick — decode slots, verify blocks, prefill-chunk rows
        — is ONE compiled launch (:meth:`_step_ragged`)."""
        with obs_trace.span("engine-step", tick=self.ticks):
            t_admit, c_admit = time.monotonic(), time.thread_time()
            with obs_trace.span("engine-admit"):
                self._admit()
            return self._step_ragged(time.monotonic() - t_admit, c_admit)

    def _prefill_budget_tokens(self) -> int:  # holds _lock
        """The policy's per-tick prefill budget, validated as TOKENS
        (ISSUE 11: the unit is pinned — a chunk-count return is a policy
        bug), floored to one chunk so prefill always advances and capped
        at the engine's geometry (``_prefill_cap``) so that the tick never
        stalls its decode rows behind a backlog of prompts."""
        budget = self.policy.prefill_budget(
            [r for r in self._prefill_q if r._phase == "prefill"],
            self._sched_state(time.monotonic()))
        if not isinstance(budget, int) or budget < 0:
            raise ValueError(
                f"prefill_budget must be a non-negative int of TOKENS, "
                f"got {budget!r}")
        return min(max(budget, self.prefill_chunk), self._prefill_cap)

    def _prepare_decode_locked(self, active,
                               ahead) -> np.ndarray:  # holds _lock
        """On-demand paging + per-slot speculation depth for the decode
        rows of this tick; mutates ``active`` in place when a row must be
        failed.  ``ahead[i]`` is 1 for a row the tick in flight is still
        sampling for: this tick feeds it one position past the host's.

        A row crossing into a block it holds no page for gets one of each
        class now (``ClassMemory.grant``; the commitment ledger guarantees
        this can't fail while the slot is in flight).  A speculating slot
        writes up to k_eff positions past its own, so its horizon covers
        the whole verify block; k_eff
        itself is per-slot and per-tick — capped by --spec_k, the tokens
        the request still owes, and (adaptive mode) the acceptance EMA.
        Writes past a row's k_eff land on the null page or above the
        accepted frontier — discarded by the acceptance mask, rewritten
        before ever being attended."""
        k_eff = np.zeros((self.max_slots,), np.int32)
        for i in list(active):
            req = self._slots[i]
            if self.spec_k:
                remaining = req.max_new_tokens - len(req.generated)
                k_i = min(self.spec_k, remaining - 1)
                if self.spec_adaptive:
                    k_i = min(k_i, max(1, int(round(
                        req._spec_ema * self.spec_k))))
                k_eff[i] = max(k_i, 0)
            last = (int(self._positions[i]) + int(ahead[i])
                    + int(k_eff[i])) // self.page_size
            for k, (cls, mem) in enumerate(zip(self._classes, req._mem)):
                got = cls.grant(mem, last)
                if got is None:  # ledger-unreachable; fail just the row
                    self._fail_locked(req, self._ledger_violated(
                        k, "an in-flight slot"))
                    active.remove(i)
                    break
                if got:
                    self._dirty = True
        return k_eff

    def _dev_state_locked(self, ahead=0, spent=()) -> Tuple:  # holds _lock
        """The device mirror of the per-slot arrays, re-uploaded from the
        host copies only when admission/retirement dirtied the layout.
        The host copies stand where the last APPLIED tick left them; with
        a tick in flight, the rows it runs (``ahead``, 0 or 1 a slot) are
        uploaded one position and one step on, and the rows whose budget
        it spends (``spent``) with a null table: a dead row, which writes
        the null page and never runs past its granted pages.  Snapshots
        go up, never the mirrors themselves: those move again before the
        tick that reads the upload has run, and a backend may alias host
        memory (XLA:CPU does)."""
        if self._dirty:
            # the tables: one a class, a single leaf where there is one
            bt = tuple(cls.snapshot(spent) for cls in self._classes)
            self._dev_state = (jax.tree.map(
                self._asarray, bt if len(bt) > 1 else bt[0]),) + tuple(
                self._asarray(a) for a in (
                    self._positions + ahead, self._tokens.copy(),
                    self._keys.copy(), self._steps + ahead,
                    self._temperature.copy(), self._top_k.copy(),
                    self._top_p.copy()))
            self._dirty = False
        return self._dev_state

    def _note_launches_locked(self, n: int,
                              prefill_tokens: int) -> None:  # holds _lock
        """Tick-phase launch accounting (ISSUE 11): ``n`` compiled
        attention programs were dispatched this step."""
        self.tick_launches += n
        self.last_tick_launches = n
        if obs_registry.publishing():
            if n:
                self._m_launches.inc(n)
            if prefill_tokens:
                self._m_prefill_per_tick.observe(prefill_tokens)
            if prefill_tokens > self.prefill_chunk:
                self._m_multi_chunk.inc()

    def _note_host_gap(self, gap: Optional[float]) -> None:
        """Record one inter-launch host gap (scheduling + emission fetch
        + apply time between device dispatches)."""
        if gap is not None and obs_registry.publishing():
            self._m_host_gap.observe(gap)

    def _apply_rows_locked(self, rec: _Launched, toks_np, logps_np,
                           now) -> int:  # holds _lock
        """Fold one tick's sampled tokens (``[b]``) into the slots.  A row
        is discarded when its slot no longer holds the launched request
        (:meth:`_row_live`: retired, preempted or failed since the launch;
        a preempted victim's discarded token regenerates bitwise on resume
        because its sampling stream is ``fold_in(key, step)`` replay);
        ``_positions`` / ``_steps`` / ``_tokens`` move here, so they
        always stand where the last applied tick left them."""
        emitted = 0
        toks, logps = toks_np.tolist(), logps_np.tolist()
        for k, (i, req) in enumerate(zip(rec.active, rec.reqs)):
            if not self._row_live(rec, k):
                continue
            room = min(req.max_new_tokens - len(req.generated),
                       self.max_seq - len(req.prompt)
                       - len(req.generated))
            if room < 1:
                continue
            tok = toks[i]
            req.generated.append(tok)
            req.log_probs.append(logps[i])
            if req._step == 0:
                req._t_first = now
                req._flight.mark_first_token(now)
                self._note_ttft_locked(now - req._t_submit)
            self._stream_emit_locked(req, req.generated[-1:],
                                     req.log_probs[-1:])
            req._step += 1
            self._positions[i] += 1
            self._tokens[i] = tok
            self._steps[i] += 1
            emitted += 1
            if room == 1 or self._stopped_by_token(req, tok):
                self._retire(i)
        return emitted

    def _land_inflight(self) -> int:
        """Apply the tick in flight, if any — the boundary synchronization
        point (a handoff export, the loop's end): after this the host
        mirrors are exact.  Returns tokens emitted."""
        emitted = 0
        while True:
            got = self._apply_tick()
            if got is None:
                return emitted
            emitted += got

    # -- the ragged tick (ISSUE 11) ----------------------------------------

    def _plan_ragged_prefill(self):  # holds _lock
        """Pack prefill-chunk rows for this tick under the policy's
        token budget.

        Chunks stay on the absolute ``prefill_chunk`` grid; multiple
        chunks — from one request or several, in the policy's prefill
        order — pack into the tick until the budget, the compiled row
        capacity, or the work runs out.  A later chunk of the same
        request may attend K/V a same-tick earlier chunk writes
        (write-then-attend holds across the whole ragged batch).  Row
        bits depend only on (token, position, horizon bucket), so ANY
        packing produces the output one chunk a tick produces.

        Returns ``(spans, pre_tok, pre_pos, pre_tables, pre_index,
        pre_hor, lp_live)`` where spans is ``[(req, start, end), ...]``,
        ``pre_tables``/``pre_index`` are the COMPRESSED block tables (one
        table per packed request, ``-1`` index = dead row; ``pre_tables``
        is one a class), and
        ``lp_live`` flags return_log_probs prompts that must take the
        teacher-forced scoring chunk instead.

        A window class's pages of a prompt are granted HERE, for the rows
        a tick packs and no further, after the window has been slid up to
        the first of them: a prompt of any length holds the window's pages
        and one tick's rows' (``window_pages_cap``).  Every other class
        holds the prompt's since admission, and its grant finds nothing
        to do."""
        Rp = self.prefill_rows
        pre_tok = np.zeros((Rp,), np.int32)
        pre_pos = np.zeros((Rp,), np.int32)
        pre_tables = tuple(
            np.full((self._pre_tables_cap, cls.width), NULL_PAGE, np.int32)
            for cls in self._classes)
        pre_index = np.full((Rp,), -1, np.int32)
        pre_hor = np.zeros((Rp,), np.int32)
        spans: List[Tuple[EngineRequest, int, int]] = []
        live = [r for r in self._prefill_q if r._phase == "prefill"]
        if len(live) != len(self._prefill_q):  # failed/cancelled
            self._prefill_q = deque(live)
        lp_live = any(r.return_log_probs for r in live)
        live = [r for r in live if not r.return_log_probs]
        if not live:
            return (spans, pre_tok, pre_pos, pre_tables, pre_index,
                    pre_hor, lp_live)
        budget = self._prefill_budget_tokens()  # at most Rp
        order = self.policy.prefill_order(
            live, self._sched_state(time.monotonic()))
        used = 0
        n_req = 0
        ps = self.page_size
        chunk = self.prefill_chunk
        for req in order:
            if n_req >= self._pre_tables_cap:
                break  # table slots exhausted; the rest wait a tick
            seq = req.seq_tokens  # resumed requests re-prefill their tail
            prompt_len = len(seq)
            fill_end = self._fill_end(prompt_len)
            pos = req._fill_pos
            if pos >= fill_end or used >= budget:
                continue
            self._slide_locked(req, pos)
            # the rows this request gets are known before they are
            # packed: chunk ends do not move what the budget leaves
            last = min(fill_end, pos + (budget - used)) - 1
            for k, (cls, mem, table) in enumerate(zip(
                    self._classes, req._mem, pre_tables)):
                if cls.grant(mem, last // ps) is None:
                    self._fail_locked(req, self._ledger_violated(
                        k, "an admitted prompt"))
                    break
                table[n_req, : len(mem.pages)] = mem.pages
            if req._phase != "prefill":     # failed just above
                continue
            while pos < fill_end and used < budget:
                # absolute-grid chunk boundary (first/last may be short);
                # a budget cut mid-chunk is fine — the next tick's chunk
                # re-anchors on the grid
                end = min(fill_end, (pos // chunk + 1) * chunk,
                          pos + (budget - used))
                for p in range(pos, end):
                    pre_tok[used] = seq[p] if p < prompt_len else 0
                    pre_pos[used] = p
                    pre_index[used] = n_req
                    pre_hor[used] = _bucket_up(p + 1)
                    used += 1
                spans.append((req, pos, end))
                pos = end
            n_req += 1
            if used >= budget:
                break
        return (spans, pre_tok, pre_pos, pre_tables, pre_index,
                pre_hor, lp_live)

    def _advance_fill_locked(self, spans) -> None:  # holds _lock
        """The half of a tick's prompt rows the host knows at dispatch:
        the packed requests' fill frontiers move to the planned ends, so
        the next plan packs the chunks after them."""
        for req, start, end in spans:
            req._fill_pos = end
            rows = end - start
            self.prefill_tokens_computed += rows
            req._flight.event("prefill_chunk", start=start, end=end,
                              rows=rows,
                              fill_end=self._fill_end(len(req.seq_tokens)))
            if obs_registry.publishing():
                self._m_prefill_tokens.inc(rows)

    def _finish_prefill_locked(self, spans, tick_s: float,
                               work_rows: int) -> None:  # holds _lock
        """The half that waits for the tick's results: a request whose
        bucketed prompt the tick completed inserts its full pages into the
        prefix trie (refeed page excluded — shared pages immutable from
        birth) and activates into decode or parks for handoff, exactly
        like _advance_scored_prefill's completion tail.  So the trie and
        an export only ever hold pages of a tick that has been fetched; a
        request waits one tick between its last chunk and its first
        decode row while ticks follow on.  ``tick_s``/``work_rows``
        attribute the fused launch's wall time to each request's flight
        record proportionally to its rows — an estimate by construction
        (the launch is ONE program), documented as such."""
        ps = self.page_size
        for req, start, end in spans:
            if req._phase != "prefill":  # failed since the launch
                continue
            if work_rows > 0:
                req._flight.add_prefill_compute(
                    tick_s * (end - start) / work_rows)
            seq = req.seq_tokens
            if end >= self._fill_end(len(seq)):
                self._prefill_q.remove(req)
                self._cache_insert_locked(req, seq, (len(seq) - 1) // ps)
                self._activate_or_handoff(req, req._slot)

    def _step_ragged(self, admit_s: float, c_admit: float) -> int:
        """One fused ragged tick: decode slots + verify blocks + packed
        prefill-chunk rows, ONE compiled attention launch
        (generation/ragged.py).  return_log_probs prompts are the one
        carve-out — their teacher-forced chunk is a program of its own
        (counted honestly in the launch telemetry).

        The step runs one tick ahead of the host: it plans and DISPATCHES
        the next tick first, then fetches and applies the tick that was
        in flight (:meth:`_apply_tick`) while the device runs the new
        one, so retire, admit, activate and preempt land one tick late
        and the device does not wait for them.  Lossless because:

        * everything the next tick needs but the continuing rows' tokens
          is known before the last tick's results are: a row it runs
          stands one position and one step on (``ahead``; the host
          mirrors keep the APPLIED state), page crossings follow from
          that, and a prompt's fill frontier moves when its chunk is
          dispatched (:meth:`_advance_fill_locked`);
        * those tokens go device to device: the tick takes the in-flight
          tick's ``next_tok`` for the rows it carries (``carried``) and
          the uploaded host token for the rest;
        * a row whose budget (``max_new_tokens``, ``max_seq``) the tick
          in flight spends is launched dead (``spent``); a row that a
          stop token ends runs one overrun row in the tick already
          launched, dropped at apply (:meth:`_row_live`), its write in
          the request's own tail page or the null page; pages a retire
          or a preemption releases are re-used only by ticks dispatched
          later, which the device runs later; a preempted victim draws
          its dropped tokens again, bit for bit (``fold_in(key, step)``);
        * what cannot be predicted keeps lag 0, by what the engine sees:
          a speculative tick (its positions move by the accepted count)
          and a step that ran a scoring chunk are applied at once.

        The phases are spans under the caller's ``engine-step``: plan,
        launch (inside ``engine-ragged-tick``) of this tick, then fetch
        and apply of the tick before, each with its ``tick=``; and one
        observation each of ``mlt_engine_tick_phase_seconds`` a tick,
        with ``admit_s`` (the caller's admission) the fifth; a step that
        launches nothing observes nothing for a launch.  Plan's three
        jobs are spans of their own under ``engine-plan``
        (``plan-prefill``, ``plan-pages``, ``plan-upload``) with
        ``mlt_engine_plan_part_seconds`` beside them.  ``c_admit`` is the
        thread's CPU clock where the caller's admission began: admit,
        plan and launch are one stretch on the thread, read again where
        the launch ends (``mlt_engine_tick_host_cpu_seconds``, side
        ``dispatch``; :meth:`_apply_tick` reads ``apply``'s pair)."""
        t_plan = time.monotonic()
        with obs_trace.span("engine-plan"):
            with obs_trace.span("plan-prefill"):
                with self._lock:
                    pre0 = self.prefill_tokens_computed
                    (spans, pre_tok, pre_pos, pre_tables, pre_index,
                     pre_hor, lp_live) = self._plan_ragged_prefill()
                did_lp = (1 if lp_live and self._advance_scored_prefill()
                          else 0)
            prefill_s = time.monotonic() - t_plan
            with self._lock:
                t_pages = time.monotonic()
                with obs_trace.span("plan-pages"):
                    prev = self._inflight[-1] if self._inflight else None
                    ahead = np.zeros((self.max_slots,), np.int32)
                    if prev is not None:
                        for k, i in enumerate(prev.active):
                            if self._row_live(prev, k):
                                ahead[i] = 1
                    active, spent = [], []
                    for i, r in enumerate(self._slots):
                        if r is None or r._phase != "decode":
                            continue
                        n = len(r.generated) + 1
                        if ahead[i] and (
                                n >= r.max_new_tokens
                                or len(r.prompt) + n >= self.max_seq):
                            spent.append(i)
                        else:
                            active.append(i)
                    if active:
                        k_eff = self._prepare_decode_locked(active, ahead)
                    else:
                        k_eff = np.zeros((self.max_slots,), np.int32)
                pages_s = time.monotonic() - t_pages
                idle = not active and not spans
                if idle:
                    self._note_launches_locked(
                        did_lp, self.prefill_tokens_computed - pre0)
                    if obs_registry.publishing():
                        self._m_active.set(0)
                        self._m_free_pages.set(self.pool.num_free)
                        self._m_pages_cached.set(
                            len(self.cache) if self.cache else 0)
                    self._publish_queued_locked()
                else:
                    no = self.ticks + len(self._inflight)
                    reqs = [self._slots[i] for i in active]
                    epochs = [r._preemptions for r in reqs]
                    # decode rows at position 0 (a one-token prompt's
                    # first): runs that start a sequence, as a prompt's
                    # first chunk is (the state class's reset counter)
                    starts = int(((self._positions + ahead)[active]
                                  == 0).sum()) if self.state else 0
                    self.peak_active_slots = max(self.peak_active_slots,
                                                 len(active))
                    if spent:
                        self._dirty = True
                    t_upload = time.monotonic()
                    with obs_trace.span("plan-upload"):
                        # a re-upload holds the tokens of the last APPLIED
                        # tick: the rows the tick in flight runs take its
                        # output instead, on the device
                        carry = ((prev.toks, self._asarray(ahead > 0))
                                 if prev is not None and self._dirty
                                 else None)
                        bt, pos, toks, keys, steps, temp, tk, tp = \
                            self._dev_state_locked(ahead, spent)
                        if carry is None:
                            carry = (toks, self._no_carry)
                    upload_s = time.monotonic() - t_upload

            n_pre = sum(end - start for _, start, end in spans)
            # live prefill rows bucketed to chunk multiples: the program's
            # one shape knob (a dead-row-free decode tick at 0; composition
            # within a bucket is pure data)
            n_bucket = (min(self.prefill_rows,
                            _bucket_up(n_pre, self.prefill_chunk))
                        if n_pre else 0)
        if idle:
            # nothing to dispatch: what is in flight lands now
            return did_lp + (self._apply_tick() is not None)
        t_tick = time.monotonic()
        gap = (None if self._last_dispatch_end is None
               else t_tick - self._last_dispatch_end)
        with obs_trace.span("engine-ragged-tick", active=len(active),
                            prefill_tokens=n_pre, launches=1,
                            k=self.spec_k, tp=self._tp), \
                self._overlap_span(), self._pp_span():
            with obs_trace.span("engine-launch", tick=no,
                                prefill_rows=n_bucket,
                                prefill_tokens=n_pre,
                                decode_rows=len(active)):
                pre_args = () if not n_bucket else (
                    self._asarray(pre_tok[:n_bucket]),
                    self._asarray(pre_pos[:n_bucket]),
                    jax.tree.map(self._asarray, pre_tables  # one: a leaf
                                 if len(pre_tables) > 1 else pre_tables[0]),
                    self._asarray(pre_index[:n_bucket]),
                    self._asarray(pre_hor[:n_bucket]))
                tick_fn = self._tick_program(n_bucket)
                moe = loop_mass = None
                if self.spec_k:
                    (self.pool.kv, self.pool.draft_kv,
                     out_tok, out_lp, acc, cnt,
                     new_pos, next_tok, new_steps) = call_tick(
                        tick_fn, self.params, self.draft_params,
                        self.pool.kv, self.pool.draft_kv,
                        bt, pos, toks, keys, steps, temp, tk, tp,
                        self._asarray(k_eff), *pre_args)
                    spec = (acc, cnt, k_eff)
                    del acc, cnt
                else:
                    (self._kv, next_tok, out_lp,
                     new_pos, new_steps, *extra) = call_tick(
                        tick_fn, self.params, self._kv,
                        bt, pos, toks, keys, steps, temp, tk, tp,
                        *carry, *pre_args)
                    out_tok, spec = next_tok, None
                    # generation/ragged.py: the router's first, a looped
                    # stack's masses last
                    if self.cfg.model.num_experts is not None:
                        moe = extra[0]
                    if self._m_loop_mass is not None:
                        loop_mass = extra[-1]
                self._last_dispatch_end = time.monotonic()
                with self._lock:
                    self._inflight.append(_Launched(
                        active, reqs, out_tok, out_lp, t_tick, epochs,
                        no=no, spans=spans, n_bucket=n_bucket, spec=spec,
                        moe=moe, loop_mass=loop_mass))
                    self._advance_fill_locked(spans)
                    if not self._dirty:
                        # steady state: the tick advanced the device mirror
                        self._dev_state = (bt, new_pos, next_tok, keys,
                                           new_steps, temp, tk, tp)
                    self._note_launches_locked(
                        1 + did_lp, self.prefill_tokens_computed - pre0)
                    if obs_registry.publishing():
                        self._m_inflight.set(len(self._inflight))
                # the launch's handles are dropped here, outside the lock:
                # freeing a device array can release the interpreter lock
                del pre_args, bt, pos, toks, keys, steps, temp, tk, tp
                del carry, prev, out_tok, out_lp, next_tok, new_pos
                del new_steps, spec, moe
        t_launched, c_launched = time.monotonic(), time.thread_time()
        self._note_host_gap(gap)
        dry = False
        for pl in self._pools:
            if pl.reclaimed:
                dry, pl.reclaimed = True, False
                if pl.page_class is not None and obs_registry.publishing():
                    self._m_dry_class[pl.page_class].inc()
        if obs_registry.publishing():
            for ph, sec in (("admit", admit_s), ("plan", t_tick - t_plan),
                            ("launch", t_launched - t_tick)):
                self._m_phase[ph].observe(sec)
            self._m_host_cpu["dispatch"].observe(c_launched - c_admit)
            for part, sec in (("prefill", prefill_s), ("pages", pages_s),
                              ("upload", upload_s)):
                self._m_plan_part[part].observe(sec)
            if dry:
                self._m_dry_ticks.inc()
        if self.state and obs_registry.publishing():
            self._note_state_rows(active, spans, n_pre, starts,
                                  pre_index[:n_bucket], pre_pos[:n_bucket])
        if self._walked and obs_registry.publishing():
            # the tick's rows as the program lays them out, by the kernel's
            # own rules: the slots in the tick's order (a slot's verify
            # rows together), a request's prompt rows behind them, on the
            # tables the launch read
            with self._lock:
                null = np.zeros((1, self.pages_per_seq), np.int32)
                tables = [
                    np.concatenate([null, mine, packed])
                    for mine, packed, _ in zip(
                        [cls.table for cls in self._classes], pre_tables,
                        self._walked)]
                pos = self._positions + ahead
            tables[0][1 + np.asarray(spent, np.int64)] = NULL_PAGE
            order = decode_order(tables[0][1:1 + self.max_slots])
            at = np.arange(self.spec_k + 1)
            on = np.zeros((self.max_slots, at.size), bool)
            on[active] = at <= k_eff[active, None]
            on, pos = on[order], pos[order, None] + at
            pre = pre_index[:n_bucket]
            rows = (
                np.concatenate([(on * (1 + order[:, None])).ravel(), np.where(
                    pre >= 0, 1 + self.max_slots + pre, 0)]),
                np.concatenate([(on * pos).ravel(), pre_pos[:n_bucket]]),
                np.concatenate([(on * _bucket_up(pos + 1)).ravel(),
                                pre_hor[:n_bucket]]))
            self._note_walks(tables, rows)
        # the tick before lands while the device runs this one; this one
        # too where the host cannot know its outcome's shape beforehand
        lag = 0 if self.spec_k or did_lp else 1
        while self._apply_tick(keep=lag) is not None:
            pass
        return len(active) + (1 if spans else 0) + did_lp

    def _note_walks(self, tables, rows) -> None:
        """The paged kernel's rows, walks and blocks of one launched tick, by
        its own rule (``tile_shares``): ``tables`` one a page class, the
        null row first; ``rows`` each row's table, position and horizon."""
        self._m_paged_rows.inc(int((rows[2] > 0).sum()))
        if self._m_sparse is not None:   # picked rows are gathered: no walk
            topk = self.cfg.model.index_topk
            ctx = (rows[1] + 1)[rows[2] > 0]
            ctx, layers = ctx[ctx > topk], self._walked[0][0]
            self._m_sparse["rows"].inc(int(ctx.size))
            self._m_sparse["scored"].inc(layers * int(ctx.sum()))
            self._m_sparse["attended"].inc(layers * topk * int(ctx.size))
            return
        for k, (table, (layers, window, row)) in enumerate(
                zip(tables, self._walked)):
            shares = tile_shares(table, *rows, window=window,
                                 page=self.page_size, row_bytes=row)
            if k == 0:
                self._m_paged_walks.inc(int(shares.walks()))
                self._m_paged_carried.inc(int(shares.carried()))
            seen, fetched = shares.blocks()
            self._m_paged_seen.inc(layers * int(seen))
            self._m_paged_fetched.inc(layers * int(fetched))

    def _note_state_rows(self, active, spans, n_pre: int, starts: int,
                         pre, pre_pos) -> None:
        """The state sweep's rows and runs of one launched tick, by the
        program's own rule (ops/retention.tick_runs): a decode row is a run
        of one, a request's prompt rows (``pre``, ``pre_pos``) one run
        however many chunks they fill, and a run at position 0 starts on
        zero (``starts``: the decode rows that do)."""
        runs = {id(r): start for r, start, _ in reversed(spans)}
        self._m_state["rows"].inc(len(active) + n_pre)
        self._m_state["touches"].inc(len(active) + len(runs))
        self._m_state["resets"].inc(
            sum(start == 0 for start in runs.values()) + starts)
        steps = len(active) + n_pre
        if self.spool.sweep_steps is not None:
            # the tick's rows as the program lays them out: a decode
            # row a slot, the prompt rows behind them, each request's
            # at consecutive positions
            row_slots = np.zeros((self.max_slots,), np.int32)
            row_slots[active] = 1 + np.asarray(active, np.int32)
            steps = self.spool.sweep_steps(
                np.concatenate([row_slots, np.where(
                    pre >= 0, 1 + self.max_slots + pre, 0)]),
                np.concatenate([np.zeros_like(row_slots), pre_pos]))
        self._m_state["steps"].inc(steps)

    def _row_live(self, rec: _Launched, k: int) -> bool:  # holds _lock
        """Whether row ``k`` of a launch in flight is still its request's:
        the slot holds that request, decoding, and not preempted since
        (a victim can be back in its old slot before the launch is
        applied; it then draws the dropped tokens again)."""
        req = rec.reqs[k]
        return (self._slots[rec.active[k]] is req
                and req._phase == "decode"
                and req._preemptions == rec.epochs[k])

    def _apply_tick(self, keep: int = 0) -> Optional[int]:
        """Fetch and fold the oldest ragged tick in flight, unless no more
        than ``keep`` are (then None): ONE batched ``jax.device_get`` for
        its emissions — the wait for that tick to end on the device, and
        the result's way back — then tokens to requests and streams, stop
        rules, retirement, prefill completion, gauges.  Returns tokens
        emitted."""
        with self._lock:
            if len(self._inflight) <= keep:
                return None
            rec = self._inflight.popleft()
            lagged = bool(self._inflight)
            dirty = self._streams_dirty_locked()
        t_fetch = time.monotonic()
        with obs_trace.span("engine-fetch", tick=rec.no):
            if dirty:
                self._kick_streams()
            handles = (rec.toks, rec.logps) + (
                rec.spec[:2] if rec.spec else ())
            if rec.moe is not None:      # rides the same fetch
                handles += (rec.moe,)
            if rec.loop_mass is not None:    # and so do these
                handles += (rec.loop_mass,)
            fetched = jax.device_get(handles)
            if rec.loop_mass is not None:
                *fetched, masses = fetched
                mass = masses[rec.active].sum(0, dtype=np.float64)
                self.loop_exit_mass += mass
                if obs_registry.publishing():
                    for m_step, at in zip(self._m_loop_mass, mass):
                        m_step.inc(float(at))
            if rec.moe is not None:
                *fetched, moe_stats = fetched
                rows, touched = int(moe_stats[0]), int(moe_stats[1])
                # a share of the experts (moe_experts_held) also says what
                # of that was its own; all of it where every expert is held
                held, held_touched = ((int(moe_stats[2]), int(moe_stats[4]))
                                      if len(moe_stats) > 2
                                      else (rows, touched))
                self.moe_assignments += rows
                self.moe_experts_touched += touched
                self.moe_held_assignments += held
                self.moe_held_experts_touched += held_touched
                # the same numbers where a capture can lay them beside
                # this tick's device time (engine-launch has its ``tick=``)
                with obs_trace.span("engine-moe", tick=rec.no,
                                    assignments=rows, touched=touched,
                                    held=held, held_touched=held_touched):
                    pass
                if obs_registry.publishing():
                    self._m_moe_assignments.inc(rows)
                    self._m_moe_touched.inc(touched)
                    self._m_moe_held.inc(held)
                    self._m_moe_held_touched.inc(held_touched)
        now, c_apply = time.monotonic(), time.thread_time()
        with obs_trace.span("engine-apply", tick=rec.no):
            with self._lock:
                # one tick of device time: from the end of the fetch
                # before (when the device took this tick up) or, with
                # nothing in flight then, from this tick's launch.
                # Feeds Retry-After/shed drain estimates
                dt = now - max(rec.t0, self._last_fetch_t)
                self._last_fetch_t = now
                self._ema_tick_s = (dt if self._ema_tick_s is None
                                    else 0.8 * self._ema_tick_s + 0.2 * dt)
                self.ticks += 1
                if self._blocks is not None:
                    emitted = self._blocks.apply_locked(rec, *fetched, now)
                elif rec.spec:
                    emitted = self._apply_spec_locked(
                        rec.active, rec.spec[2], *fetched, now)
                else:
                    emitted = self._apply_rows_locked(
                        rec, fetched[0], fetched[1], now)
                self._finish_prefill_locked(
                    rec.spans, dt,
                    sum(end - start for _, start, end in rec.spans)
                    + len(rec.active))
                self.ticked_tokens += emitted
                self._note_seq_pages_locked()
                if obs_registry.publishing():
                    self._m_ticks.inc()
                    self._m_tokens.inc(emitted)
                    self._m_apply_lag["1" if lagged else "0"].inc()
                    self._m_tick_kind[
                        "prefill" if rec.n_bucket else "decode"].inc()
                    self._m_inflight.set(len(self._inflight))
                    self._m_active.set(
                        sum(r is not None and r._phase == "decode"
                            for r in self._slots))
                    self._m_free_pages.set(self.pool.num_free)
                    self._m_pages_cached.set(
                        len(self.cache) if self.cache else 0)
                    for pl in self._pools:
                        pl.publish_states()
                self._publish_queued_locked()
            # This tick's device handles are dropped here, inside the span
            # and outside the lock.  Freeing a device array can release the
            # interpreter lock, and the wait to get it back from the
            # handler threads apply has just woken (a request's first
            # token, its end; no longer one a streamed token) is apply's
            # doing, so it is counted as apply, in the span and in the
            # phase histogram alike.
            del rec, handles
        if obs_registry.publishing():
            t_end, c_end = time.monotonic(), time.thread_time()
            self._m_phase["fetch"].observe(now - t_fetch)
            self._m_phase["apply"].observe(t_end - now)
            self._m_host_cpu["apply"].observe(c_end - c_apply)
        if self.ticks == 1:     # the first tick has landed: start-up is over
            print(f"[engine] {obs_compiles.summary()}", flush=True)
        return emitted

    # What a block model (generation/blocks.py) asks of admission; a causal
    # model takes the first branch of each.

    def _new_request(self, prompt, max_new_tokens: int,
                     kw: dict) -> EngineRequest:
        if self._blocks is not None:
            return self._blocks.new_request(prompt, max_new_tokens, kw)
        for name in ("denoising_steps", "remasking_strategy",
                     "confidence_threshold"):
            if name in kw:
                raise gen.InvalidRequest(
                    f"{name} is a block model's (diffusion_block_length): "
                    "this model decodes one token a step")
        return EngineRequest(prompt=prompt, max_new_tokens=max_new_tokens,
                             **kw)

    def _first_pages(self, left: int) -> int:
        """Pages granted at admission beyond the prompt's: the first decode
        page; every page of a block model's output (its table does not move
        under a tick in flight)."""
        return max(left, 0) if self._blocks is not None else int(left > 0)

    def _parkable_pages(self, req: EngineRequest, seq) -> int:
        """Whole pages of a preempted request's ``seq`` the prefix trie may
        hold: those ``seq[:-1]`` covers (the resume's refeed tick writes the
        last token's again); a block model's committed blocks'."""
        if self._blocks is not None:
            return self._blocks.parkable_pages(req, seq)
        return (len(seq) - 1) // self.page_size

    def _note_seq_pages_locked(self) -> None:  # holds _lock
        """Once an applied tick: slide the decoding sequences' windows up
        to the position their next query stands at (the tick in flight
        feeds the token this apply has just appended), and add the pages
        they then hold, a class, to ``mlt_engine_seq_pages_sum``."""
        live = [r for r in self._slots
                if r is not None and r._phase == "decode"]
        if any(cls.window for cls in self._classes):
            released = sum(
                self._slide_locked(r, len(r.prompt) + len(r.generated) - 1)
                for r in live)
            if released:
                with obs_trace.span("pool-slide", released=released):
                    pass
        if live and obs_registry.publishing():
            self._m_seq_ticks.inc(len(live))
            for k, (cls, m) in enumerate(zip(self._classes,
                                             self._m_seq_pages)):
                m.inc(sum(cls.held(r._mem[k]) for r in live))

    def run_until_idle(self) -> None:
        """Drive ticks on the calling thread until queue and slots drain.
        Safe under concurrent callers: one drives at a time, the rest take
        over as the lock frees (their requests are served either way)."""
        while True:
            with self._drive_lock:
                n = self.step()
            if n == 0:
                with self._lock:
                    if not self._queue and all(
                            r is None for r in self._slots):
                        return

    # -- background scheduler ---------------------------------------------

    def start(self) -> None:
        """Run the scheduler loop in a daemon thread (server mode)."""
        if self._thread is not None:
            return
        # under _work: a racing stop() must not interleave between this
        # write and the thread starting (found by graftcheck's
        # lock-discipline rule — the write was bare)
        with self._work:
            self._stopping = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._work:
            self._stopping = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def _idle_locked(self) -> bool:  # holds _lock
        # a tick in flight keeps the loop stepping: its apply may retire
        # rows (and must not be stranded when every slot empties before
        # it lands)
        return (not self._queue and not self._inflight
                and all(r is None for r in self._slots))

    def _loop(self) -> None:
        while True:
            with self._work:
                # a live /profile window does not sleep through idleness:
                # the loop comes out to close it (below) and waits then
                while (not self._stopping and self._idle_locked()
                       and not self.profile_trigger.active):
                    # "no work" in a capture, told from "host slow"
                    with obs_trace.span("engine-wait"):
                        self._work.wait()
                if self._stopping:
                    break
                out_of_work = self._idle_locked()
            with self._drive_lock:
                if out_of_work:
                    # the window asked for N ticks and the traffic ended
                    # first: it ends here, not N ticks into the next burst
                    self.profile_trigger.close()
                    continue
                # GET /profile?ticks=N (generation/server.py): a capture
                # brackets whole steps, started and stopped here
                self.profile_trigger.maybe_start(self.ticks)
                try:
                    applied = self.ticks
                    self.step()
                    # a window of N ticks closes when N have landed (a
                    # step lands the tick before the one it launches)
                    for _ in range(self.ticks - applied):
                        self.profile_trigger.step_done()
                except Exception as e:  # noqa: BLE001 — boundary: the
                    # scheduler thread must outlive a failed step, or every
                    # waiter hangs until its timeout with no answer
                    traceback.print_exc()
                    self._fail_all(e)
        with self._drive_lock:
            self._land_inflight()
            self.profile_trigger.close()

    def _fail_all(self, e: Exception) -> None:
        """A step raised (a program failed to lower, compile or run):
        nothing in flight can be trusted, so every queued and resident
        request fails with the step's error — each waiter gets its answer
        (a 500 from the server) now.  Counted in ``engine_failures``."""
        with self._lock:
            self.failures += 1
            self._inflight.clear()
            pending = list(self._queue) + [
                r for r in self._slots if r is not None]
            self._queue.clear()
            self._prefill_q.clear()
            for req in pending:
                self._fail_locked(req, e)
            self._publish_queued_locked()
        if obs_registry.publishing():
            self._m_failures.inc()

    # -- server-facing API (api.InferenceEngine surface) -------------------

    def generate_and_post_process(
        self,
        prompts: Sequence[str],
        tokens_to_generate: int = 0,
        return_output_log_probs: bool = False,
        top_k_sampling: int = 0,
        top_p_sampling: float = 0.0,
        temperature: float = 1.0,
        add_BOS: bool = False,
        use_eod_token_for_early_termination: bool = True,
        stop_on_double_eol: bool = False,
        stop_on_eol: bool = False,
        random_seed: int = -1,
        priority: int = 1,
        ttft_deadline_ms: Optional[float] = None,
        tpot_deadline_ms: Optional[float] = None,
        trace_id: str = "",
        **block_kw,
    ):
        """Drop-in for api.generate_and_post_process: tokenize, submit each
        prompt as its own request (all of them share decode ticks), wait,
        detokenize.  ``tokens_to_generate == 0`` (scoring mode) delegates to
        the dense-path scorer."""
        tok = self.tokenizer
        if tokens_to_generate == 0:
            return self._legacy().generate_and_post_process(
                prompts, 0, return_output_log_probs=True, add_BOS=add_BOS)

        termination_id = getattr(self.cfg.model, "eos_id", None) or tok.eod
        bos = getattr(tok, "bos_token_id", None) or getattr(tok, "bos", None)
        reqs = []
        for i, prompt in enumerate(prompts):
            ids = tok.tokenize(prompt)
            if add_BOS:
                ids = [bos if bos is not None else tok.eod] + ids
            reqs.append(self.submit(
                ids, tokens_to_generate,
                temperature=temperature, top_k=top_k_sampling,
                top_p=top_p_sampling, termination_id=termination_id,
                use_eod_for_termination=use_eod_token_for_early_termination,
                stop_on_double_eol=stop_on_double_eol,
                stop_on_eol=stop_on_eol,
                seed=None if random_seed == -1 else random_seed + i,
                return_log_probs=return_output_log_probs,
                priority=priority,
                ttft_deadline_ms=ttft_deadline_ms,
                tpot_deadline_ms=tpot_deadline_ms,
                trace_id=trace_id,
                **block_kw,
            ))
        if self._thread is None:
            self.run_until_idle()
        rows = [r.result(timeout=600) for r in reqs]

        lengths = [len(t) for t, _ in rows]
        width = max(lengths)
        tokens = np.zeros((len(rows), width), np.int32)
        for i, (t, _) in enumerate(rows):
            tokens[i, : len(t)] = t
        tokens, texts, segments = detokenize_generations(
            tok, tokens, np.asarray(lengths), True)
        if return_output_log_probs:
            log_probs = [
                (r.prompt_log_probs or []) + r.log_probs for r in reqs]
            log_probs = [
                lp[: len(seg) - 1] for lp, seg in zip(log_probs, segments)]
        else:
            log_probs = None
        return texts, segments, log_probs, tokens

    def submit_stream_request(
        self,
        prompt: str,
        tokens_to_generate: int,
        return_output_log_probs: bool = False,
        top_k_sampling: int = 0,
        top_p_sampling: float = 0.0,
        temperature: float = 1.0,
        add_BOS: bool = False,
        stop_on_double_eol: bool = False,
        stop_on_eol: bool = False,
        use_eod_token_for_early_termination: bool = True,
        random_seed: int = -1,
        priority: int = 1,
        ttft_deadline_ms: Optional[float] = None,
        tpot_deadline_ms: Optional[float] = None,
        trace_id: str = "",
        stream_events: int = 256,
        **block_kw,
    ):
        """``submit_stream`` with ``generate_and_post_process``'s exact
        tokenization and submit kwargs for ONE prompt — the streamed
        request must sample the identical token sequence the buffered
        path would (same seed handling, same termination id), or the
        ``done`` event could not carry the identical body."""
        tok = self.tokenizer
        if tokens_to_generate < 1:
            raise ValueError("streaming requires tokens_to_generate >= 1")
        termination_id = getattr(self.cfg.model, "eos_id", None) or tok.eod
        bos = getattr(tok, "bos_token_id", None) or getattr(tok, "bos", None)
        ids = tok.tokenize(prompt)
        if add_BOS:
            ids = [bos if bos is not None else tok.eod] + ids
        return self.submit_stream(
            ids, tokens_to_generate,
            stream_events=stream_events,
            temperature=temperature, top_k=top_k_sampling,
            top_p=top_p_sampling, termination_id=termination_id,
            use_eod_for_termination=use_eod_token_for_early_termination,
            stop_on_double_eol=stop_on_double_eol,
            stop_on_eol=stop_on_eol,
            seed=None if random_seed == -1 else random_seed,
            return_log_probs=return_output_log_probs,
            priority=priority,
            ttft_deadline_ms=ttft_deadline_ms,
            tpot_deadline_ms=tpot_deadline_ms,
            trace_id=trace_id,
            **block_kw,
        )

    def finalize_stream_request(self, req: EngineRequest,
                                return_output_log_probs: bool = False):
        """Post-process one FINISHED streamed request with the exact
        ``generate_and_post_process`` tail (same padding, detokenization
        and log-prob slicing), so a streamed ``done`` body and the
        buffered response for the same request are token-identical.
        Returns ``(texts, segments, log_probs)``."""
        assert req.finished and not req.error, "request not cleanly finished"
        tok = self.tokenizer
        row = list(req.prompt) + req.generated
        tokens = np.zeros((1, len(row)), np.int32)
        tokens[0, :] = row
        tokens, texts, segments = detokenize_generations(
            tok, tokens, np.asarray([len(row)]), True)
        if return_output_log_probs:
            log_probs = [(req.prompt_log_probs or []) + req.log_probs]
            log_probs = [
                lp[: len(seg) - 1] for lp, seg in zip(log_probs, segments)]
        else:
            log_probs = None
        return texts, segments, log_probs

    # -- cross-replica KV handoff (ISSUE 19, serving/handoff/) -------------

    def refuse_handoff(self) -> None:
        """The handoff's wire format names pages of keys and values: a
        state, a latent row or a layer pattern says so in a sentence."""
        refuse_unserved(self.cfg, handoff=True)

    def prefill_and_export(self, prompt, *, add_BOS: bool = False,
                           trace_id: str = "", timeout_s: float = 600.0):
        """Prefill ``prompt`` (str — tokenized exactly like
        ``generate_and_post_process`` — or token ids) WITHOUT decoding,
        and export its full KV pages as a handoff wire blob.

        The request runs the normal admission/chunked-prefill path
        (trie hits included) but parks in the ``handoff`` phase instead
        of activating into decode; the export reads its pages under
        ``_drive_lock`` (serialized against tick dispatch — ticks
        donate the pool buffers) while the request's refs keep the
        bytes stable, then retires it — prompt pages stay in the trie
        cached-idle, so repeated long prompts skip recompute on the
        prefill tier too.  Only FULL pages the refeed tick never writes
        are exported (``(len(prompt) - 1) // page_size``, the exact
        ``PrefixCache.insert`` rule), so the receiving trie can share
        them as immutable from birth.

        Returns ``(blob, info)`` — ``info`` has ``tokens`` / ``pages``
        / ``bytes`` / ``hit_tokens`` for the migration receipt."""
        self.refuse_handoff()
        from megatron_llm_tpu.serving.handoff import wire

        tok = self.tokenizer
        if isinstance(prompt, str):
            bos = (getattr(tok, "bos_token_id", None)
                   or getattr(tok, "bos", None))
            ids = tok.tokenize(prompt)
            if add_BOS:
                ids = [bos if bos is not None else tok.eod] + ids
        else:
            ids = [int(t) for t in prompt]
        req = self.submit(ids, 1, top_k=1, use_eod_for_termination=False,
                          prefill_only=True, trace_id=trace_id)
        if self._thread is None:
            self.run_until_idle()
        if not req._done.wait(timeout_s):
            raise TimeoutError("handoff prefill did not finish in time")
        if req.shed:
            raise RequestShed(req.error or "request shed",
                              retry_after=req.shed_retry_after)
        if req.error:
            raise RuntimeError(req.error)
        ps = self.page_size
        n = (len(ids) - 1) // ps
        blob = None
        pages: List[int] = []
        try:
            with self._drive_lock:
                with self._lock:
                    pages = list(req._mem[0].pages[:n])
                leaves = self.pool.export_pages(pages)
            blob = wire.encode_pages(ids[: len(pages) * ps], ps,
                                     self.kv_dtype, leaves)
        finally:
            with self._lock:
                if blob is not None:
                    req._flight.event("kv_export", pages=len(pages),
                                      bytes=len(blob))
                    if obs_registry.publishing():
                        self._m_kv_export_pages.inc(len(pages))
                        self._m_kv_export_bytes.inc(len(blob))
                self._finish_handoff_locked(req, pages=len(pages))
        return blob, {"tokens": len(pages) * ps, "pages": len(pages),
                      "bytes": len(blob), "hit_tokens": req._hit_tokens}

    def export_cached_kv(self, tokens, *, trace_id: str = ""):
        """Export the longest trie-cached prefix of ``tokens`` (ids) as
        a handoff blob — the migration path for state that is already
        parked in the prefix cache (e.g. a preempted request's finished
        pages).  Returns ``(blob, n_pages)``; ``n_pages`` may be 0 when
        nothing is cached."""
        self.refuse_handoff()
        from megatron_llm_tpu.serving.handoff import wire

        if self.cache is None:
            raise ValueError("prefix cache disabled; nothing to export")
        tokens = [int(t) for t in tokens]
        ps = self.page_size
        with self._drive_lock:
            with self._lock:
                matched = self.cache.match(tokens, len(tokens) // ps)
            try:
                leaves = self.pool.export_pages(matched)
            finally:
                with self._lock:
                    self.pool.release(matched)
        blob = wire.encode_pages(tokens[: len(matched) * ps], ps,
                                 self.kv_dtype, leaves)
        if matched and obs_registry.publishing():
            with self._lock:
                self._m_kv_export_pages.inc(len(matched))
                self._m_kv_export_bytes.inc(len(blob))
        return blob, len(matched)

    def import_kv(self, blob: bytes, *, trace_id: str = "") -> dict:
        """Install a pushed handoff blob: decode the wire format,
        allocate pages for the UNCACHED suffix (trie incumbents win —
        dedup is free), upload the exact bytes, and register the pages
        via ``PrefixCache.insert`` + release — they end cached-idle,
        indistinguishable from a locally prefilled-then-parked prefix,
        so COW/refcount/eviction invariants hold unchanged.  Raises
        :class:`EngineOverloaded` (→ 503 + Retry-After) when the pool
        cannot hold the pages.  Returns the import receipt."""
        self.refuse_handoff()
        from megatron_llm_tpu.serving.handoff import wire

        payload = wire.decode_pages(blob)
        if self.cache is None:
            raise ValueError("prefix cache disabled; cannot import KV pages")
        if payload.page_size != self.page_size:
            raise ValueError(
                f"handoff page_size {payload.page_size} != engine "
                f"page_size {self.page_size}")
        if payload.kv_dtype != self.kv_dtype:
            raise ValueError(
                f"handoff kv_dtype {payload.kv_dtype!r} != engine "
                f"kv_dtype {self.kv_dtype!r}")
        n = payload.n_pages
        rec = self.flight.open(trace_id, kind="kv_import", pages=n)
        try:
            if n == 0:
                return {"pages": 0, "installed": 0, "deduped": 0,
                        "tokens": 0}
            with self._drive_lock:
                with self._lock:
                    matched = self.cache.match(payload.tokens, n)
                    covered = len(matched)
                    fresh = (self.pool.alloc(n - covered)
                             if covered < n else [])
                    if fresh is None:
                        self.pool.release(matched)
                        raise EngineOverloaded(
                            f"KV pool cannot hold {n - covered} "
                            f"pushed pages",
                            retry_after=self._drain_eta(
                                len(self._queue)),
                            info=self._overload_info())
                try:
                    if fresh:
                        # device upload outside _lock: the fresh
                        # pages are refcount-1 and unshared, and
                        # _drive_lock serializes vs tick dispatch
                        self.pool.import_pages(fresh, {
                            name: arr[:, covered:]
                            for name, arr in payload.leaves.items()})
                except Exception:
                    with self._lock:
                        self.pool.release(matched)
                        self.pool.release(fresh)
                    raise
                with self._lock:
                    installed = self.cache.insert(
                        payload.tokens, matched + fresh, n)
                    # inserted pages go cached-idle; duplicates
                    # (trie incumbents won the position) go free
                    self.pool.release(matched)
                    self.pool.release(fresh)
                    if obs_registry.publishing():
                        self._m_kv_import_pages.inc(installed)
                        self._m_kv_import_bytes.inc(len(blob))
            receipt = {"pages": n, "installed": installed,
                       "deduped": n - installed,
                       "tokens": len(payload.tokens)}
            rec.event("kv_import", bytes=len(blob), **receipt)
            rec.finish("ok")
            return receipt
        except Exception as e:  # noqa: BLE001 — record then surface
            rec.finish("error", error=f"{type(e).__name__}: {e}")
            raise
        finally:
            self.flight.close(rec)

    def _refuse_static(self, what: str) -> None:
        if self._blocks is not None:
            raise ValueError(
                f"{what} is not written for a model that generates by "
                "diffusion over blocks (diffusion_block_length "
                f"{self.cfg.model.diffusion_block_length}): its static "
                "generation path decodes one token a step under a causal "
                "mask. Ask the engine for tokens_to_generate >= 1.")

    def _legacy(self):
        """A dense-path InferenceEngine view over the SAME (already
        quantized) params — bypasses __init__ so int8 weights are not
        re-quantized."""
        from megatron_llm_tpu.generation.api import InferenceEngine

        self._refuse_static("the dense single-stream path (prompt scoring, "
                            "beam search)")
        legacy = InferenceEngine.__new__(InferenceEngine)
        legacy.cfg, legacy.params, legacy.tokenizer = (
            self.cfg, self.params, self.tokenizer)
        return legacy

    def beam_search_and_post_process(self, *args, **kw):
        """Beam search stays on the dense single-stream path (api.py)."""
        return self._legacy().beam_search_and_post_process(*args, **kw)
