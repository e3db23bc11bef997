"""The fused RAGGED engine tick: one compiled program per engine geometry
runs a whole tick's heterogeneous work — decode slots, speculative-verify
blocks, and prefill chunks — as a single flattened row batch (ISSUE 11,
PAPERS.md "Ragged Paged Attention").

A split dispatch would compile up to three shapes of the same
computation per tick: the decode batch, one program per prefill-chunk
geometry, and the speculative verify.  Here the tick is ONE ragged batch
of single-token rows; each row carries its own data-carried ``(token,
position, block-table row, kv horizon)``:

* a **decode slot** contributes 1 row (span 1) at its own position;
* a **speculative-verify block** contributes ``spec_k + 1`` consecutive
  rows (span k+1) — the PR 9 flattened-batch construction, now just an
  ordinary span in the ragged batch rather than a special-cased program;
* a **prefill chunk** contributes ``rows`` consecutive rows (span =
  chunk), one per prompt position, writing K/V through the request's
  block table exactly like the chunked-prefill path.

Every op in the forward is then structurally an s=1 paged decode over a
larger batch, and per-row bits are BATCH-SIZE INVARIANT (the PR 9 key
numerics fact) — so a decode row, a verify row and a prefill row compute
what a decode tick, a flattened verify and a chunk program of their own
would (masked attention is invariant to query-row partitioning when kv
horizons stay on the BUCKET(64) grid — the PR 5 contract).  That is what
makes a request's output — tokens AND log-probs, greedy AND sampled,
cache on/off — independent of what else the tick carries: the same as
the request served alone, and for greedy rows the dense single-stream
path's (tests/test_ragged_tick.py, tests/parity.py).

``prefill_rows`` is the COMPILED prefill-row capacity (a static, like
``max_slots``); which rows are live each tick is pure data.
``make_ragged_tick_fn(cfg, None, 0, 0)`` is the pure decode tick and
``make_ragged_tick_fn(cfg, draft_cfg, k, 0)`` the flattened spec verify
this module absorbed from ``speculative/verify.py``.

Write-then-attend causality holds across the whole ragged batch: all R
rows' K/V lands first (each row a distinct (page, offset) — different
requests own disjoint writable pages, consecutive rows of one request
write consecutive positions), then every row attends causally ``<= its
position``.  A prefill row may therefore attend K/V written by an earlier
row of the SAME tick (its own chunk's prefix, or an earlier chunk of the
same request packed into the same tick) — the property that lets the
token-level prefill budget run multiple chunks per tick in one launch.

A model that keeps a recurrent STATE a sequence and no keys (power
retention, ops/retention.py) runs the same tick on another contract: a
state cannot be addressed by position, so nothing "lands first".  A row's
table is one entry wide and names its state SLOT (0: a dead row), and a
RUN of rows (consecutive rows of one slot at consecutive positions:
``ops/retention.tick_runs``, read from the ``table_index`` / ``positions``
the tick already carries) reads the slot's state once, gives its rows
their outputs in order, and writes the state once; a run at position 0
starts on a zero state.  Two requests' runs are independent; a request's
own rows of one tick are ONE run however many chunks they fill.  What a
request's output is independent of is restated for this class: the same
chunking of its prompt gives the same bits whatever else the ticks carry;
ANOTHER chunking (the budget cut its prompt at other rows) sums the
state's part and the run's part in another order, and its log-probs agree
to float32 rounding (~1e-5), not bitwise.

Key discipline is unchanged from speculative/verify.py: every random draw
derives from ``base = fold_in(request_key, steps)`` fanned out through
disjoint DRAFT/ACCEPT/EMIT streams; no key is ever consumed twice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.sampling import (
    filtered_logits_per_slot,
    sample_per_slot,
)
from megatron_llm_tpu.generation.speculative.verify import (
    ACCEPT_STREAM,
    DRAFT_STREAM,
    EMIT_STREAM,
    speculative_acceptance,
)
from megatron_llm_tpu.models.language_model import (
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.models.transformer import layer_kinds, pool_classes
from megatron_llm_tpu.ops.paged_attention import PagedState, plan_walks


def row_horizons(positions: jax.Array) -> jax.Array:
    """Per-row kv horizon for LIVE rows: ``position + 1`` bucketed up to
    the BUCKET(64) grid — the same bucketing the chunked-prefill path
    applies to its attended page horizon, kept here so ragged bits depend
    only on (tokens, positions), never on tick composition."""
    b = gen.BUCKET
    return ((positions // b) + 1) * b


def decode_order(block_tables):
    """The order a tick runs its decode slots in: ``[b]`` slot ids, the
    slots that name the same first page of the class that keeps every key
    side by side (sequences on one cached prefix name the same pages, and
    rows that stand in one tile of the paged kernel walk them ONCE:
    ops/pallas/paged_attention.tile_shares), dead slots (the null table)
    last, slot order otherwise.  The rows of a tick are independent through
    the whole forward and everything a slot keeps is addressed by its
    table, so the order moves no number.  numpy on the host (the engine
    counts the walks of the tick it planned), traced in the tick."""
    first = block_tables[:, 0]
    last = np.iinfo(np.int32).max
    if isinstance(first, jax.Array):
        return jnp.argsort(jnp.where(first == 0, last, first), stable=True)
    return np.argsort(np.where(first == 0, last, first), kind="stable")


def make_ragged_tick_fn(cfg, draft_cfg, spec_k: int, prefill_rows: int,
                        *, tp: int = 1, mesh=None):
    """Build the fused ragged tick the engine compiles once per geometry.

    ``mesh`` (with ``--tp_overlap ring``) activates the chunked
    collective-matmul interception (parallel/overlap.py) for every
    forward in the tick — target, draft and prefill rows alike; the
    engine keys its compiled-program cache on the effective mode, so
    overlap and non-overlap engines never share executables.

    Returned signature, ``spec_k >= 1`` (draft model present)::

        (params, draft_params, pool_kv, draft_kv,
         block_tables, positions, tokens, req_keys, steps,
         temperature, top_k, top_p, k_eff
         [, pre_tok, pre_pos, pre_tables, pre_index, pre_hor])
        -> (pool_kv, draft_kv,
            emit [b, K+1], emit_logp [b, K+1], accepted [b], counts [b],
            new_pos, new_tok, new_steps)

    and ``spec_k == 0`` (no draft args, plain per-slot sampling)::

        (params, pool_kv, block_tables, positions, tokens,
         req_keys, steps, temperature, top_k, top_p, carry_tok, carried
         [, pre_tok, pre_pos, pre_tables, pre_index, pre_hor])
        -> (pool_kv, next_tok, logp, new_pos, new_steps
            [, moe_stats] [, loop_mass])

    ``pool_kv`` is the paged pool, ONE leaf over all layers whose row
    ops/kv_quant.py owns (K/V or latent); the engine donates it and every
    layer updates its pages in place.  A patterned model (the spec-0 tick
    only) has one leaf a page class, ``pool_kv`` the tuple of them, and
    ``block_tables`` / ``pre_tables`` tuples of tables beside it
    (models/transformer.py ``pool_classes``): a layer reads its class's.

    ``moe_stats`` exists iff the model has experts: ``[2]`` float32, the
    router's assignments (rows x topk, every row the program ran, dead
    padding rows too) and the distinct experts that received a row, summed
    over the expert layers; on the tick's one fetch to ``mlt_engine_moe_*``.
    A looped stack adds ``loop_mass`` LAST: the slots' exit masses a pass.
    Where the program holds a share of the experts (``moe_experts_held``)
    it is ``[5]``: then the held assignments that ran, those dropped for
    want of a row, and the distinct held experts that received one.

    ``carry_tok`` / ``carried`` (``[b]`` int32 / bool) feed a row its
    token device to device: the engine launches this tick before it has
    fetched the one in flight, whose ``next_tok`` is the input of every
    row that tick was sampling for (``carried``); the uploaded ``tokens``
    hold the rest.  The speculative tick has no such operands: its
    positions advance by the accepted count, which only the fetch tells
    the host, so it is never launched ahead.

    The ``pre_*`` operands exist iff ``prefill_rows > 0``: ``pre_tok`` /
    ``pre_pos`` / ``pre_hor`` are ``[prefill_rows]``; block tables come
    COMPRESSED — ``pre_tables`` is ``[T_pre, max_pages_per_seq]`` (one
    row per packed prefilling request) and ``pre_index`` maps each
    prefill row to its request's table (``-1`` = dead row).  Inside, the
    program assembles the tick's unique-table set ``[null] + slot tables
    + pre_tables`` and a per-row index — rows of one span share one
    table, so the jnp fallback gathers each table's pages exactly once
    (ops/paged_attention.paged_attention_ragged) and the Pallas kernel
    resolves ``tables[index[row], page]`` in its scalar-prefetch index
    map.  Dead prefill rows carry horizon 0, the null table and position
    0 — their writes land in garbage that is never attended, exactly
    like idle decode slots.  All of it is traced data: ANY tick
    composition — 6 decoding slots + 1 prefilling chunk + 1 verify
    block, or all-decode, or all-prefill — re-dispatches the same
    executable.
    """
    from megatron_llm_tpu.parallel import overlap as tp_overlap_mod
    from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

    ovl = tp_overlap_mod.overlap_params(cfg, mesh)
    ppc = pp_serve_mod.serve_params(cfg, mesh)
    K = spec_k
    vocab = cfg.model.vocab_size
    scope_t = ("ragged-fwd" if tp == 1 else f"ragged-fwd-tp{tp}") \
        if prefill_rows else \
        (("verify-fwd" if tp == 1 else f"verify-fwd-tp{tp}") if K
         else ("decode-fwd" if tp == 1 else f"decode-fwd-tp{tp}"))
    scope_d = "draft-fwd" if tp == 1 else f"draft-fwd-tp{tp}"

    # a model that keeps pages runs its decode slots in decode_order; one
    # that keeps a state a sequence and no page has nothing to lay side by
    # side
    classes = pool_classes(cfg)
    paged = not all(cls.state for cls in classes)
    # a page class's attention layers all call the paged kernel on the
    # tick's tables and rows under one window: which walks it shares is
    # worked out once a class in front of them (on one chip; a shard of
    # the heads or a stage's microbatch reads it off its own call)
    latent = bool(cfg.model.mla)
    planned = {} if tp > 1 or ppc is not None or cfg.model.index_topk else {
        c: layer_kinds(cfg)[cls.places[0]].window
        for c, cls in enumerate(classes) if not cls.state}

    def with_walks(state, pool_kv):
        classed = isinstance(state.block_tables, tuple)
        walks = [None] * len(classes)
        for c, window in planned.items():
            walks[c] = plan_walks(
                pool_kv[c] if classed else pool_kv,
                state._replace(block_tables=state.block_tables[c])
                if classed else state,
                cfg.model.kv_channels, sliding_window=window,
                latent=latent)
        return state._replace(walks=tuple(walks) if classed else walks[0])

    moe = cfg.model.num_experts is not None
    # what of the router's aux vector rides the fetch (models/moe.py)
    moe_stats = slice(2, 7) if moe and (
        cfg.model.experts_held < cfg.model.num_experts) else slice(2, 4)

    def target_forward(params, pool_kv, tbl, idx, pos, tok, hor):
        """ONE target forward over the full ragged batch — the single
        attention launch of the tick.  ``tbl`` is the tick's compressed
        unique-table set, ``idx`` each row's table.  Returns the router's
        aux vector as well (models/moe.py)."""
        with jax.named_scope(scope_t):
            logits, pool_kv, aux = model_forward(
                cfg, params, tok[:, None],
                position_ids=pos[:, None],
                rope_cache=make_rope_cache(cfg),
                kv_caches=pool_kv,
                paged=with_walks(PagedState(tbl, pos, hor, idx), pool_kv),
                return_aux=True,
            )
        return logits[:, 0], pool_kv, aux

    def spec_tick(params, draft_params, pool_kv, draft_kv,
                  block_tables, positions, tokens, req_keys, steps,
                  temperature, top_k, top_p, k_eff,
                  pre_tok=None, pre_pos=None, pre_tables=None,
                  pre_index=None, pre_hor=None):
        b = tokens.shape[0]
        W = block_tables.shape[1]
        null_tbl = jnp.zeros((1, W), block_tables.dtype)
        rope_d = make_rope_cache(draft_cfg)
        base = jax.vmap(jax.random.fold_in)(req_keys, steps)   # [b, 2]
        greedy_row = top_k == 1

        # ---- draft prefill rows (speculating engines keep BOTH caches
        # filled for every prefilled page, so trie-matched pages carry
        # valid draft K/V — the chunk_spec contract, fused in-program) ----
        if prefill_rows:
            d_idx = jnp.where(pre_index >= 0, 1 + pre_index, 0)
            with jax.named_scope(scope_d):
                _, draft_kv = model_forward(
                    draft_cfg, draft_params, pre_tok[:, None],
                    position_ids=pre_pos[:, None], rope_cache=rope_d,
                    kv_caches=draft_kv,
                    paged=PagedState(
                        jnp.concatenate([null_tbl, pre_tables]),
                        pre_pos, pre_hor, d_idx))

        # ---- 1) draft k tokens (sequential s=1 draft forwards) ----
        # The scan runs K+1 steps, not K: step j < K samples draft token
        # d_{j+1}; the final step feeds d_K at position pos+K purely for
        # its K/V WRITE (its sample is discarded) — without it an
        # all-accepted-plus-bonus tick leaves a permanent hole in the
        # draft cache at d_K's position (the PR 9 acceptance-decay bug).
        def draft_step(carry, j):
            tok, dkv = carry
            pos_j = positions + j
            # rows past their own depth write to the NULL page: a clipped
            # write at the end of the sequence budget would otherwise land
            # inside the row's LAST real page and corrupt live KV
            bt_j = jnp.where((j <= k_eff)[:, None], block_tables, 0)
            with jax.named_scope(scope_d):
                logits, dkv = model_forward(
                    draft_cfg, draft_params, tok[:, None],
                    position_ids=pos_j[:, None], rope_cache=rope_d,
                    kv_caches=dkv,
                    paged=PagedState(bt_j, pos_j))
            filt, greedy = filtered_logits_per_slot(
                logits[:, -1], top_k=top_k, top_p=top_p,
                temperature=temperature, vocab_size=vocab)
            keys_j = jax.vmap(lambda kb: jax.random.fold_in(
                jax.random.fold_in(kb, DRAFT_STREAM), j))(base)
            drawn = jax.vmap(lambda k_, row: jax.random.categorical(k_, row))(
                keys_j, filt)
            nxt = jnp.where(greedy_row, greedy, drawn).astype(jnp.int32)
            return (nxt, dkv), (nxt, filt)

        (_, draft_kv), (draft_seq, q_seq) = jax.lax.scan(
            draft_step, (tokens, draft_kv), jnp.arange(K + 1))
        draft_toks = jnp.moveaxis(draft_seq[:K], 0, 1)   # [b, K]
        q_filt = jnp.moveaxis(q_seq[:K], 0, 1)           # [b, K, v]

        # ---- 2) target verify + prefill: ONE ragged forward ----
        # verify blocks are ordinary span-(K+1) entries: row (slot i,
        # offset j) feeds one token at position pos_i + j with slot i's
        # block table; prefill rows append after them.
        S = K + 1
        block = jnp.concatenate([tokens[:, None], draft_toks], axis=1)
        flat_tok = block.reshape(b * S)
        flat_pos = (positions[:, None]
                    + jnp.arange(S)[None, :]).reshape(b * S)
        # compressed tables: [null] + the b slot tables (+ the packed
        # prefilling requests' tables).  Null-table routing replaces the
        # old per-row bt masking: verify rows past a slot's depth are
        # discarded by the acceptance mask, and their writes must never
        # clip into a live page at the budget edge
        live = (jnp.arange(S)[None, :] <= k_eff[:, None]).reshape(b * S)
        slot_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), S)
        flat_idx = jnp.where(live, 1 + slot_ids, 0)
        flat_hor = row_horizons(flat_pos)
        # the slots' verify blocks in decode_order, a block's rows together
        order = decode_order(block_tables)
        flat_tok, flat_pos, flat_idx, flat_hor = (
            a.reshape(b, S)[order].reshape(b * S)
            for a in (flat_tok, flat_pos, flat_idx, flat_hor))
        if prefill_rows:
            all_tok = jnp.concatenate([flat_tok, pre_tok])
            all_pos = jnp.concatenate([flat_pos, pre_pos])
            all_idx = jnp.concatenate(
                [flat_idx,
                 jnp.where(pre_index >= 0, 1 + b + pre_index, 0)])
            all_tbl = jnp.concatenate([null_tbl, block_tables, pre_tables])
            all_hor = jnp.concatenate([flat_hor, pre_hor])
        else:
            all_tok, all_pos, all_idx, all_hor = (
                flat_tok, flat_pos, flat_idx, flat_hor)
            all_tbl = jnp.concatenate([null_tbl, block_tables])
        out, pool_kv, _ = target_forward(
            params, pool_kv, all_tbl, all_idx, all_pos, all_tok, all_hor)
        # [b, K+1, v_padded], back in slot order
        t_logits = out[: b * S].reshape(b, S, -1)[jnp.argsort(order)]

        rep = lambda x: jnp.repeat(x, S, axis=0)  # noqa: E731
        t_filt_flat, t_greedy_flat = filtered_logits_per_slot(
            t_logits.reshape(b * S, -1), top_k=rep(top_k), top_p=rep(top_p),
            temperature=rep(temperature), vocab_size=vocab)
        t_filt = t_filt_flat.reshape(b, S, -1)
        t_greedy = t_greedy_flat.reshape(b, S)

        # ---- 3) lossless acceptance ----
        u = jax.vmap(lambda kb: jax.random.uniform(
            jax.random.fold_in(kb, ACCEPT_STREAM), (K,)))(base)
        emit_keys = jax.vmap(
            lambda kb: jax.random.fold_in(kb, EMIT_STREAM))(base)
        accepted, counts, emit = speculative_acceptance(
            draft_toks, q_filt, t_filt, t_greedy, greedy_row, k_eff,
            u, emit_keys)

        # reported per-token log-probs come from the RAW target logits,
        # exactly like the non-speculative tick's gather
        emit_logp = gen._gather_token_log_probs(t_logits, emit)

        new_pos = positions + counts
        new_steps = steps + counts
        new_tok = jnp.take_along_axis(
            emit, (counts - 1)[:, None], axis=1)[:, 0]
        return (pool_kv, draft_kv, emit, emit_logp,
                accepted, counts, new_pos, new_tok, new_steps)

    def tick(params, pool_kv, block_tables, positions, tokens,
             req_keys, steps, temperature, top_k, top_p,
             carry_tok, carried,
             pre_tok=None, pre_pos=None, pre_tables=None,
             pre_index=None, pre_hor=None):
        b = tokens.shape[0]

        def with_null(*tables):     # a class's tables behind its null table
            null_tbl = jnp.zeros((1, tables[0].shape[1]), tables[0].dtype)
            return jnp.concatenate([null_tbl, *tables])

        all_tok = jnp.where(carried, carry_tok, tokens)
        all_pos = positions
        all_idx = 1 + jnp.arange(b, dtype=jnp.int32)
        if paged:
            order = decode_order(jax.tree.leaves(block_tables)[0])
            all_tok, all_pos, all_idx = (
                a[order] for a in (all_tok, all_pos, all_idx))
        all_hor = row_horizons(all_pos)
        if prefill_rows:
            all_tok = jnp.concatenate([all_tok, pre_tok])
            all_pos = jnp.concatenate([all_pos, pre_pos])
            all_idx = jnp.concatenate(
                [all_idx, jnp.where(pre_index >= 0, 1 + b + pre_index, 0)])
            all_tbl = jax.tree.map(with_null, block_tables, pre_tables)
            all_hor = jnp.concatenate([all_hor, pre_hor])
        else:
            all_tbl = jax.tree.map(with_null, block_tables)
        out, pool_kv, aux = target_forward(
            params, pool_kv, all_tbl, all_idx, all_pos, all_tok, all_hor)
        last = out[:b]
        keys = jax.vmap(jax.random.fold_in)(req_keys, steps)
        if paged:
            # a row is sampled where it stands, with its slot's key and
            # settings; only the [b] results go back to slot order
            keys, top_k, top_p, temperature = (
                a[order] for a in (keys, top_k, top_p, temperature))
        next_tok = sample_per_slot(
            keys, last, top_k=top_k, top_p=top_p,
            temperature=temperature, vocab_size=cfg.model.vocab_size)
        logp = gen._gather_token_log_probs(last, next_tok)
        if paged:
            next_tok, logp = (jnp.zeros_like(a).at[order].set(a)
                              for a in (next_tok, logp))
        res = (pool_kv, next_tok, logp, positions + 1, steps + 1)
        mass = ()
        if cfg.model.loop_steps > 1:
            # a looped stack's aux is a pair (models/language_model.py): the
            # routers' vector and the rows' exit distributions, of which the
            # sampled rows' [b, passes] ride the same fetch in slot order
            aux, mass = aux
            mass = (jnp.zeros_like(mass[:b, 0]).at[order].set(mass[:b, 0]),)
        return res + ((aux[moe_stats],) if moe else ()) + mass

    base_fn = spec_tick if K else tick
    if ovl is None and ppc is None:
        return base_fn

    def overlapped(*args, **kw):
        # trace-time contexts: every model_forward in the tick — target,
        # draft scan, prefill rows — routes its row-parallel projections
        # through the ring and/or its layer stack through the pp stage
        # pipeline while this builder's closure is being traced
        with tp_overlap_mod.activate(ovl), pp_serve_mod.activate(ppc):
            return base_fn(*args, **kw)

    return overlapped



def block_driver(engine):
    """The engine's driver for a model that generates by diffusion over
    blocks (generation/blocks.py, whose tick stands in this one's place),
    None for every other model: here so that the engine's one import of
    this module finds it, and nothing of it is loaded for a causal model."""
    if not engine.cfg.model.diffusion_block_length:
        return None
    from megatron_llm_tpu.generation.blocks import BlockDriver

    return BlockDriver(engine)
