"""Generation by diffusion over blocks (SDAR): the engine's tick for a model
whose ``diffusion_block_length`` is set.  A model without it runs none of
this.

Such a model does not emit one token a sequence a step.  Its sequence is cut
into blocks of ``B`` positions from position 0; its attention mask is
BLOCK-causal (a query sees every key up to the end of its own block); and it
generates a block at a time: the block starts as ``B`` copies of the row
``mask_token_id`` of the vocabulary (the prompt's remainder, if the prompt
ends inside the block, as known tokens), and a DENOISING STEP is one forward
of the block's ``B`` positions against the kept K/V of all earlier blocks
and the block's own fresh K/V, logits at all ``B`` positions with NO shift
(the logits at a position are over the token AT it), a sample ``x0`` and its
confidence ``softmax(logits)[x0]`` a position, and some of the masked
positions taking their ``x0`` (:func:`unmask`).  When none is masked the
block is run ONCE MORE with its final tokens, and that pass's K/V are what
later blocks read (the COMMIT pass).

What a tick is here (:func:`make_block_tick_fn`).  A generating slot
contributes a span of ``2 B`` rows: the ``B`` commit rows of the block it
finished in the tick before (dead otherwise) and the ``B`` denoise rows of
its current block.  Write-then-attend holds over the whole ragged batch, so
the commit rows' K/V has landed before the next block's rows attend: the
commit rides on the next block's first step and costs no tick of its own.
Every row of a block has ONE mask position (its block's last) and its own
rotary and write position: ``PagedState.positions`` carries the first,
``write_positions`` the second, and the paged kernel and its fallback mask
as they always did.  A slot's span is one kernel tile and all its rows name
ONE table, so the kernel's grouping rule
(ops/pallas/paged_attention.tile_shares: a span of one table's rows is
walked whole, whatever their positions) serves the denoise rows and the
commit rows beside them by ONE walk through the last compute block any of
them sees, each row under its own mask; no row walks a block alone.
Commit rows skip the head; so do prompt rows.

Whether a position is masked is its KNOWN flag, never ``id ==
mask_token_id``: a prompt may hold that id and random weights may emit it.

The slot's block (its start, ids, known flags, the block to commit) lives
ON THE DEVICE beside the pool and moves there: the engine launches a tick
before the one in flight has reached the host, and the number of tokens a
step unmasks is data under ``low_confidence_dynamic``.  The host uploads a
slot's state when the slot changes hands (``fresh``) and otherwise reads:
each tick returns what it sampled, which positions it unmasked and the block
start it ran at, and :meth:`BlockDriver.apply_locked` appends a request's
newly CONTIGUOUS known tokens in position order, so a stream never carries a
token before the ones left of it.  ``max_new_tokens`` and a stop token cut
inside a block.  A token's log-probability is that of the step that
unmasked it.

A prompt is prefilled to its last block boundary (chunks stay on the
``prefill_chunk`` grid, a multiple of ``B``); the remainder opens the first
generated block as known tokens.  Every page of prompt + output is granted
at admission (the table does not move under a tick in flight).  The prefix
trie holds whole pages (``page_size % B == 0``: a page's K/V depends on no
token behind it) of prefilled prompt blocks and, at a preemption, of
COMMITTED blocks; a preempted request keeps those and draws the block in
flight again from its prompt-known positions (greedy: the same tokens, bit
for bit; what it had streamed is not streamed twice).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.launch import call_tick
from megatron_llm_tpu.generation.pools import NULL_PAGE
from megatron_llm_tpu.generation.sampling import sample_with_log_prob
from megatron_llm_tpu.models.language_model import (
    compute_logits,
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.models.transformer import (
    block_mask_position,
    pool_classes,
)
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.observability import trace as obs_trace
from megatron_llm_tpu.ops.attention import announce_path
from megatron_llm_tpu.ops.paged_attention import PagedState, plan_walks

STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")
# the published script's defaults for the checkpoint (`assumed`)
DEFAULT_STRATEGY = "low_confidence_dynamic"
DEFAULT_THRESHOLD = 0.9


class BlockState(NamedTuple):
    """A slot's block, ``[slots, ...]`` on the device (and the host's
    upload of it for a slot that changed hands)."""

    start: object    # [b] int32: the current block's first position
    ids: object      # [b, B] int32: its tokens where known
    known: object    # [b, B] bool
    cids: object     # [b, B] int32: the block before, to commit
    cpend: object    # [b] bool: ... whether it still has to be
    end: object      # [b] int32: prompt + max_new_tokens
    live: object     # [b] bool
    steps: object    # [b] int32: denoising steps taken (the PRNG stream)


class Unmasking(NamedTuple):
    """Per-slot sampling and unmasking parameters, ``[slots]`` each."""

    temperature: object
    top_k: object
    top_p: object
    strategy: object     # int32 index into STRATEGIES
    per_step: object     # int32: tokens a step unmasks at least (B / steps)
    threshold: object    # float32 (low_confidence_dynamic)


def unmask(known, live, conf, strategy, per_step, threshold):
    """Which masked positions take their sample this step, ``[b, B]`` bool.

    ``sequential``: the leftmost ``per_step`` masked ones.
    ``low_confidence_static``: the ``per_step`` most confident (ties: the
    leftmost).  ``low_confidence_dynamic``: every one whose confidence
    passes ``threshold``, and at least those ``per_step``."""
    masked = ~known & live[:, None]
    n = per_step[:, None]
    seq = masked & (jnp.cumsum(masked, axis=1) <= n)
    at = jnp.arange(known.shape[1])
    score = jnp.where(masked, conf, -1.0)
    # a position's rank by confidence: how many stand before it
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    top = masked & (before.sum(axis=2) < n)
    dyn = top | (masked & (conf > threshold[:, None]))
    s = strategy[:, None]
    return jnp.where(s == 0, seq, jnp.where(s == 1, top, dyn))


def make_block_tick_fn(cfg, prefill_rows: int):
    """The block model's ragged tick, compiled once a prompt-row bucket::

        (params, pool_kv, block_tables, state, fresh, fresh_state,
         req_keys, unmasking [, pre_tok, pre_pos, pre_tables, pre_index])
        -> (pool_kv, x0 [b, B], logp [b, B], newly [b, B],
            ran [b, 3] (start, live, commit rows ran), state [, moe_stats])

    ``state`` is the tick before's (:class:`BlockState`), ``fresh`` ``[b]``
    says which slots take ``fresh_state`` instead (the host's upload: a
    slot that changed hands).  Which rows are live is data; the compiled
    block-row capacity is ``slots x 2 B``."""
    m = cfg.model
    B, mask_id = m.diffusion_block_length, m.mask_token_id
    classes = pool_classes(cfg)
    assert len(classes) == 1 and not classes[0].state
    moe = m.num_experts is not None
    moe_stats = slice(2, 7) if moe and (
        m.experts_held < m.num_experts) else slice(2, 4)
    announce_path("paged_blocks", "tile_shares",
                  f"a block's {B} rows name one table and one mask position")

    def tick(params, pool_kv, block_tables, state, fresh, fresh_state,
             req_keys, un, pre_tok=None, pre_pos=None, pre_tables=None,
             pre_index=None):
        b = fresh.shape[0]
        st = BlockState(*(
            jnp.where(fresh.reshape((b,) + (1,) * (new.ndim - 1)), new, old)
            for new, old in zip(fresh_state, state)))
        at = jnp.arange(B, dtype=jnp.int32)
        d_pos = st.start[:, None] + at
        with jax.named_scope("block_commit"):
            # the block before, once more with its final tokens: these
            # rows' K/V is what every later block reads
            c_live = jnp.broadcast_to((st.live & st.cpend)[:, None], (b, B))
            c_tok, c_pos = st.cids, d_pos - B
        d_tok = jnp.where(st.known, st.ids, mask_id)
        d_live = jnp.broadcast_to(st.live[:, None], (b, B))
        live = jnp.concatenate([c_live, d_live], axis=1).reshape(-1)
        tok = jnp.concatenate([c_tok, d_tok], axis=1).reshape(-1)
        pos = jnp.where(live, jnp.concatenate(
            [c_pos, d_pos], axis=1).reshape(-1), 0)
        slot = jnp.repeat(jnp.arange(b, dtype=jnp.int32), 2 * B)
        idx = jnp.where(live, 1 + slot, 0)
        null_tbl = jnp.zeros((1, block_tables.shape[1]), block_tables.dtype)
        if prefill_rows:
            p_live = pre_index >= 0
            tok = jnp.concatenate([tok, pre_tok])
            pos = jnp.concatenate([pos, pre_pos])
            live = jnp.concatenate([live, p_live])
            idx = jnp.concatenate(
                [idx, jnp.where(p_live, 1 + b + pre_index, 0)])
            tbl = jnp.concatenate([null_tbl, block_tables, pre_tables])
        else:
            tbl = jnp.concatenate([null_tbl, block_tables])
        # ONE mask position a block; the rotary and the write keep `pos`
        mpos = block_mask_position(pos, B)
        hor = jnp.where(live, (mpos // gen.BUCKET + 1) * gen.BUCKET, 0)
        paged = PagedState(tbl, mpos, hor, idx, write_positions=pos)
        paged = paged._replace(walks=plan_walks(
            pool_kv, paged, m.kv_channels))
        with jax.named_scope("ragged-fwd" if prefill_rows else "decode-fwd"):
            hidden, pool_kv, aux = model_forward(
                cfg, params, tok[:, None], position_ids=pos[:, None],
                rope_cache=make_rope_cache(cfg), kv_caches=pool_kv,
                paged=paged, return_aux=True, logits_postprocess=False)
        # the head on the denoise rows alone
        h = hidden[: b * 2 * B, 0].reshape(b, 2 * B, -1)[:, B:]
        with jax.named_scope("lm_head_loss"):
            logits = compute_logits(cfg, params, h.reshape(b * B, -1))
        with jax.named_scope("block_unmask"):
            rep = lambda a: jnp.repeat(a, B, axis=0)  # noqa: E731
            keys = jax.vmap(jax.random.fold_in)(req_keys, st.steps)
            keys = jax.vmap(lambda k: jax.vmap(
                lambda j: jax.random.fold_in(k, j))(at))(keys)
            x0, logp = sample_with_log_prob(
                keys.reshape(b * B, -1), logits, top_k=rep(un.top_k),
                top_p=rep(un.top_p), temperature=rep(un.temperature),
                vocab_size=m.vocab_size)
            x0, logp = x0.reshape(b, B), logp.reshape(b, B)
            newly = unmask(st.known, st.live, jnp.exp(logp), un.strategy,
                           un.per_step, un.threshold)
            ids = jnp.where(newly, x0, st.ids)
            known = st.known | newly
            # a block is done when every position the request still needs
            # is known: the last block may end on max_new_tokens
            done = st.live & (known | (d_pos >= st.end[:, None])).all(axis=1)
            last = st.start + B >= st.end
            nxt = (done & ~last)[:, None]
            new = BlockState(
                start=jnp.where(nxt[:, 0], st.start + B, st.start),
                ids=jnp.where(nxt, mask_id, ids),
                known=known & ~nxt,
                cids=jnp.where(nxt, ids, st.cids),
                cpend=nxt[:, 0],
                end=st.end,
                live=st.live & ~(done & last),
                steps=st.steps + 1)
            ran = jnp.stack([st.start, st.live.astype(jnp.int32),
                             c_live[:, 0].astype(jnp.int32)], axis=1)
        res = (pool_kv, x0, logp, newly, ran, new)
        return res + (aux[moe_stats],) if moe else res

    return tick


def request_class(base):
    """``EngineRequest`` with a block request's fields."""
    @dataclasses.dataclass
    class BlockRequest(base):
        denoising_steps: Optional[int] = None
        remasking_strategy: str = DEFAULT_STRATEGY
        confidence_threshold: float = DEFAULT_THRESHOLD
        # engine-filled: position -> (token, log-prob) of the tokens a tick
        # unmasked that the stream has not reached yet
        _unmasked: dict = dataclasses.field(default_factory=dict, repr=False)

    return BlockRequest


class BlockDriver:
    """The host's half: the engine's step for a block model (plan, launch,
    and :meth:`apply_locked` under the engine's own fetch and apply), on
    the engine's slots, pools, queues, lock and instruments."""

    def __init__(self, engine):
        e = self.e = engine
        m = e.cfg.model
        self.B = B = m.diffusion_block_length
        if e.page_size % B or e.prefill_chunk % B or e.max_seq % B:
            raise ValueError(
                f"diffusion_block_length {B} must divide page_size "
                f"{e.page_size}, prefill_chunk {e.prefill_chunk} and "
                f"engine_max_seq {e.max_seq}: a cached page and a prompt "
                "chunk end on a block boundary")
        from megatron_llm_tpu.generation.engine import EngineRequest

        self.Request = request_class(EngineRequest)
        s = e.max_slots
        # who the device's state of a slot belongs to: (request, epoch)
        self._owner: List[Optional[Tuple]] = [None] * s
        self._fns = {}
        self._up = None         # tables, keys, unmasking as last uploaded
        z = lambda *shape, dt=np.int32: np.zeros(shape, dt)  # noqa: E731
        self._dead = BlockState(z(s), z(s, B), z(s, B, dt=np.bool_),
                                z(s, B), z(s, dt=np.bool_), z(s),
                                z(s, dt=np.bool_), z(s))
        # the device's BlockState after the last launch; a tick that takes
        # no slot's state from the host
        self._state = jax.tree.map(e._asarray, self._dead)
        self._no_fresh = (e._asarray(np.zeros((s,), np.bool_)), self._state)
        reg = obs_registry.get_registry()
        self._m = {
            "denoise_rows": reg.counter(
                "mlt_engine_block_denoise_rows_total",
                help="denoise rows run: B a live slot a tick, a row a "
                     "position of its block, masked or known"),
            "commit_rows": reg.counter(
                "mlt_engine_block_commit_rows_total",
                help="commit rows run: B a finished block, its final "
                     "tokens once more for the K/V later blocks read"),
            "steps": reg.counter(
                "mlt_engine_block_steps_total",
                help="denoising steps: slot-steps, one a live slot a tick"),
            "slot_ticks": reg.counter(
                "mlt_engine_block_slot_ticks_total",
                help="ticks in which a slot had live block rows"),
            "committed": reg.counter(
                "mlt_engine_blocks_committed_total",
                help="blocks whose commit rows ran"),
            "unmasked": reg.counter(
                "mlt_engine_block_tokens_unmasked_total",
                help="positions that took their sample, whether or not "
                     "the stream has reached them"),
            "recomputed": reg.counter(
                "mlt_engine_block_recomputed_total",
                help="blocks drawn again from their prompt-known positions "
                     "after a preemption"),
        }
        # the engine's seams
        e._step_ragged = self.step
        e._fill_end = self.fill_end

    # -- what admission asks ------------------------------------------------

    def fill_end(self, prompt_len: int) -> int:
        """Prefill stops at the prompt's last block boundary; the remainder
        opens the first generated block as known tokens."""
        return prompt_len // self.B * self.B

    def new_request(self, prompt, max_new_tokens: int, kw: dict):
        steps = kw.get("denoising_steps")
        strategy = kw.get("remasking_strategy", DEFAULT_STRATEGY)
        if strategy not in STRATEGIES:
            raise gen.InvalidRequest(
                f"remasking_strategy must be one of {STRATEGIES}")
        if steps is not None and (
                not isinstance(steps, int) or steps < 1 or self.B % steps):
            raise gen.InvalidRequest(
                f"denoising_steps must divide the block length {self.B}")
        return self.Request(prompt=prompt, max_new_tokens=max_new_tokens,
                            **kw)

    def parkable_pages(self, req, seq) -> int:
        """Whole pages of ``seq`` whose K/V is final: below the block in
        flight.  The stream holds a block's every token only once a tick
        that found it whole has been applied, and that block's commit rows
        are in the tick launched before that apply: ahead, on the device,
        of any tick that reads what the trie is offered here."""
        return self.fill_end(len(seq)) // self.e.page_size

    # -- the step -----------------------------------------------------------

    def _program(self, pre_rows: int):
        fn = self._fns.get(pre_rows)
        if fn is None:
            e = self.e
            statics = ("engine_block_tick", e.max_slots, e.pages_per_seq,
                       e.page_size, e.pool.num_pages, e.pool.kv_statics,
                       pre_rows, e._pre_tables_cap, e._mesh_statics)
            fn = gen.cached_jit(
                e.cfg, "engine_block_tick", statics,
                lambda: make_block_tick_fn(e.cfg, pre_rows),
                donate_argnums=(1,))
            from megatron_llm_tpu.observability import compiles

            self._fns[pre_rows] = fn
            return compiles.startup_phase("tick-program", rows=pre_rows)(fn)
        return fn

    def _plan_prefill(self):  # called under the engine's lock
        """Prompt rows for this tick, as the engine packs them
        (``_plan_ragged_prefill``): chunks on the absolute ``prefill_chunk``
        grid under the policy's token budget, every cut on a block
        boundary (a block's rows attend each other's keys, written in the
        same tick)."""
        e, B = self.e, self.B
        Rp = e.prefill_rows
        pre_tok, pre_pos = np.zeros((Rp,), np.int32), np.zeros((Rp,), np.int32)
        pre_tables = np.full((e._pre_tables_cap, e.pages_per_seq), NULL_PAGE,
                             np.int32)
        pre_index = np.full((Rp,), -1, np.int32)
        spans = []
        live = [r for r in e._prefill_q if r._phase == "prefill"]
        if len(live) != len(e._prefill_q):
            e._prefill_q = deque(live)
        if not live:
            return spans, pre_tok, pre_pos, pre_tables, pre_index
        budget = e._prefill_budget_tokens() // B * B
        order = e.policy.prefill_order(live, e._sched_state(time.monotonic()))
        used = n_req = 0
        chunk = e.prefill_chunk
        for req in order:
            if n_req >= e._pre_tables_cap or used >= budget:
                break
            seq = req.seq_tokens
            fill_end, pos = self.fill_end(len(seq)), req._fill_pos
            if pos >= fill_end:
                continue
            pages = req._mem[0].pages
            pre_tables[n_req, : len(pages)] = pages
            while pos < fill_end and used < budget:
                end = min(fill_end, (pos // chunk + 1) * chunk,
                          pos + (budget - used))
                n = end - pos
                pre_tok[used:used + n] = seq[pos:end]
                pre_pos[used:used + n] = np.arange(pos, end)
                pre_index[used:used + n] = n_req
                used += n
                spans.append((req, pos, end))
                pos = end
            n_req += 1
        return spans, pre_tok, pre_pos, pre_tables, pre_index

    def _fresh_locked(self):  # called under the engine's lock
        """The slots whose device state is no longer their holder's, and
        the state each takes: a request that has just been activated starts
        its first block at its last block boundary with the prompt's
        remainder known; an emptied slot is dead."""
        e, B = self.e, self.B
        fresh = np.zeros((e.max_slots,), np.bool_)
        up = None                   # made when the first slot needs it
        for i, r in enumerate(e._slots):
            owner = None
            if r is not None and r._phase == "decode":
                owner = (r, r._preemptions)
            held = self._owner[i]
            if owner is held or (
                    owner is not None and held is not None
                    and owner[0] is held[0] and owner[1] == held[1]):
                continue
            self._owner[i] = owner
            fresh[i] = True
            if up is None:
                up = BlockState(*(a.copy() for a in self._dead))
            if owner is None:
                continue
            seq = r.seq_tokens
            start = self.fill_end(len(seq))
            up.start[i] = start
            # what the prompt holds of the block; a resumed request's own
            # tokens of it are drawn again
            n_known = max(0, min(B, len(r.prompt) - start))
            up.ids[i, :n_known] = r.prompt[start:start + n_known]
            up.known[i, :n_known] = True
            up.end[i] = len(r.prompt) + r.max_new_tokens
            up.live[i] = True
            up.steps[i] = r._step
            r._unmasked.clear()
            if r._preemptions:
                self._m["recomputed"].inc()
        return fresh, up

    def _unmasking_locked(self) -> Unmasking:  # called under the engine's lock
        e, B = self.e, self.B
        s = e.max_slots
        strategy, per_step = np.zeros((s,), np.int32), np.full((s,), B, np.int32)
        threshold = np.ones((s,), np.float32)
        for i, r in enumerate(e._slots):
            if r is None or r._phase != "decode":
                continue
            strategy[i] = STRATEGIES.index(r.remasking_strategy)
            per_step[i] = B // (r.denoising_steps or B)
            threshold[i] = r.confidence_threshold
        return Unmasking(e._temperature.copy(), e._top_k.copy(),
                         e._top_p.copy(), strategy, per_step, threshold)

    def step(self, admit_s: float, c_admit: float) -> int:
        """One block tick, in the place of ``_step_ragged`` and with its
        phases, spans and instruments: plan and DISPATCH the next tick, then
        fetch and apply the one in flight beside it.  The device carries
        every slot's block from tick to tick, so the launch waits for
        nothing the tick in flight decides."""
        from megatron_llm_tpu.generation.engine import _Launched, _bucket_up

        e = self.e
        t_plan = time.monotonic()
        with obs_trace.span("engine-plan"):
            with obs_trace.span("plan-prefill"):
                with e._lock:
                    pre0 = e.prefill_tokens_computed
                    (spans, pre_tok, pre_pos, pre_tables,
                     pre_index) = self._plan_prefill()
            prefill_s = time.monotonic() - t_plan
            with e._lock:
                t_pages = time.monotonic()
                with obs_trace.span("plan-pages"):
                    active = [i for i, r in enumerate(e._slots)
                              if r is not None and r._phase == "decode"]
                pages_s = time.monotonic() - t_pages
                idle = not active and not spans
                if idle:
                    e._note_launches_locked(0, 0)
                    if obs_registry.publishing():
                        e._m_active.set(0)
                        e._m_free_pages.set(e.pool.num_free)
                        e._m_pages_cached.set(len(e.cache) if e.cache else 0)
                    e._publish_queued_locked()
                else:
                    no = e.ticks + len(e._inflight)
                    reqs = [e._slots[i] for i in active]
                    epochs = [r._preemptions for r in reqs]
                    e.peak_active_slots = max(e.peak_active_slots,
                                              len(active))
                    t_upload = time.monotonic()
                    with obs_trace.span("plan-upload"):
                        fresh, up = self._fresh_locked()
                        tables = e._classes[0].snapshot()
                        if e._dirty or self._up is None or fresh.any():
                            self._up = (
                                e._asarray(tables),
                                e._asarray(e._keys.copy()),
                                jax.tree.map(e._asarray,
                                             self._unmasking_locked()))
                            e._dirty = False
                        bt, keys, un = self._up
                        fresh_args = self._no_fresh if not fresh.any() else (
                            e._asarray(fresh), jax.tree.map(e._asarray, up))
                    upload_s = time.monotonic() - t_upload
            n_pre = sum(end - start for _, start, end in spans)
            n_bucket = (min(e.prefill_rows,
                            _bucket_up(n_pre, e.prefill_chunk))
                        if n_pre else 0)
        if idle:
            return int(e._apply_tick() is not None)
        t_tick = time.monotonic()
        gap = (None if e._last_dispatch_end is None
               else t_tick - e._last_dispatch_end)
        with obs_trace.span("engine-ragged-tick", active=len(active),
                            prefill_tokens=n_pre, launches=1, k=0, tp=1):
            with obs_trace.span("engine-launch", tick=no,
                                prefill_rows=n_bucket, prefill_tokens=n_pre,
                                decode_rows=len(active)):
                pre_args = () if not n_bucket else (
                    e._asarray(pre_tok[:n_bucket]),
                    e._asarray(pre_pos[:n_bucket]),
                    e._asarray(pre_tables),
                    e._asarray(pre_index[:n_bucket]))
                (e._kv, x0, logp, newly, ran, self._state,
                 *moe) = call_tick(
                    self._program(n_bucket), e.params, e._kv, bt,
                    self._state, *fresh_args, keys, un, *pre_args)
                e._last_dispatch_end = time.monotonic()
                with e._lock:
                    e._inflight.append(_Launched(
                        active, reqs, x0, logp, t_tick, epochs, no=no,
                        spans=spans, n_bucket=n_bucket,
                        spec=(newly, ran, (tables, pre_tables,
                                           pre_index[:n_bucket],
                                           pre_pos[:n_bucket])),
                        moe=moe[0] if moe else None))
                    e._advance_fill_locked(spans)
                    e._note_launches_locked(
                        1, e.prefill_tokens_computed - pre0)
                    if obs_registry.publishing():
                        e._m_inflight.set(len(e._inflight))
                del pre_args, x0, logp, newly, ran, moe, fresh_args
        t_launched, c_launched = time.monotonic(), time.thread_time()
        e._note_host_gap(gap)
        dry = e.pool.reclaimed
        e.pool.reclaimed = False
        if obs_registry.publishing():
            for ph, sec in (("admit", admit_s), ("plan", t_tick - t_plan),
                            ("launch", t_launched - t_tick)):
                e._m_phase[ph].observe(sec)
            e._m_host_cpu["dispatch"].observe(c_launched - c_admit)
            for part, sec in (("prefill", prefill_s), ("pages", pages_s),
                              ("upload", upload_s)):
                e._m_plan_part[part].observe(sec)
            if dry:
                e._m_dry_ticks.inc()
        while e._apply_tick(keep=1) is not None:
            pass
        return len(active) + (1 if spans else 0)

    # -- what a fetched tick folds into --------------------------------------

    def apply_locked(self, rec, x0, logp, newly, ran,
                     now) -> int:  # called under the engine's lock
        """Fold one tick: the positions it unmasked go to their requests,
        and each request's stream takes the tokens that have become
        CONTIGUOUS with what it holds, in position order.  A row is dropped
        when its slot no longer holds the launched request
        (``_row_live``)."""
        e, B = self.e, self.B
        emitted = 0
        self._count(rec, newly, ran)
        for k, (i, req) in enumerate(zip(rec.active, rec.reqs)):
            if not e._row_live(rec, k) or not ran[i, 1]:
                continue
            start = int(ran[i, 0])
            for j in np.flatnonzero(newly[i]):
                req._unmasked[start + int(j)] = (int(x0[i, j]),
                                                 float(logp[i, j]))
            req._step += 1
            at = len(req.prompt) + len(req.generated)
            for p in [p for p in req._unmasked if p < at]:
                del req._unmasked[p]    # a resumed block's, streamed before
            toks, lps = [], []
            room = min(req.max_new_tokens - len(req.generated),
                       e.max_seq - at)
            stop = room < 1
            while not stop and at in req._unmasked:
                tok, lp = req._unmasked.pop(at)
                toks.append(tok)
                lps.append(lp)
                req.generated.append(tok)
                req.log_probs.append(lp)
                at += 1
                stop = len(toks) == room or e._stopped_by_token(req, tok)
            if toks:
                if req._t_first == 0.0:
                    req._t_first = now
                    req._flight.mark_first_token(now)
                    e._note_ttft_locked(now - req._t_submit)
                e._stream_emit_locked(req, toks, lps)
                emitted += len(toks)
            if stop:
                e._retire(i)
        return emitted

    def _count(self, rec, newly, ran) -> None:
        """The block counters, and the paged kernel's rows, walks and
        blocks by its own rules on the rows this tick ran (the host learns
        which slots were live, and which committed, from the tick)."""
        e, B = self.e, self.B
        if not obs_registry.publishing():
            return
        live, commit = ran[:, 1] > 0, ran[:, 2] > 0
        m = self._m
        m["denoise_rows"].inc(B * int(live.sum()))
        m["commit_rows"].inc(B * int(commit.sum()))
        m["steps"].inc(int(live.sum()))
        m["slot_ticks"].inc(int((live | commit).sum()))
        m["committed"].inc(int(commit.sum()))
        m["unmasked"].inc(int(newly[live].sum()))
        tables, pre_tables, pre_index, pre_pos = rec.spec[2]
        s = e.max_slots
        at = np.arange(B)
        on = np.concatenate([np.repeat(commit[:, None], B, 1),
                             np.repeat(live[:, None], B, 1)], axis=1)
        pos = np.concatenate([ran[:, :1] - B + at, ran[:, :1] + at], axis=1)
        idx = np.concatenate([
            (on * (1 + np.arange(s))[:, None]).ravel(),
            np.where(pre_index >= 0, 1 + s + pre_index, 0)])
        mpos = block_mask_position(
            np.concatenate([(on * pos).ravel(), pre_pos]), B)
        hor = np.where(idx > 0, (mpos // gen.BUCKET + 1) * gen.BUCKET, 0)
        rows = (idx.astype(np.int32), (mpos * (idx > 0)).astype(np.int32),
                hor.astype(np.int32))
        e._note_walks([np.concatenate([
            np.zeros((1, e.pages_per_seq), np.int32), tables, pre_tables])],
            rows)
