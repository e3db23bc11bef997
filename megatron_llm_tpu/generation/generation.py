"""Autoregressive generation — TPU-native redesign of
megatron/text_generation/generation.py + forward_step.py.

Reference design: a python loop over positions, one forward per token, with
per-step host synchronization and PP broadcasts
(generation.py:89-285, forward_step.py:44-204).

TPU design: the whole decode — prefill + token loop + early termination —
is ONE jitted program built around ``lax.while_loop``; tokens never leave
the device until generation finishes, so there is no host round-trip per
token.  The KV cache is a stacked ``[L, b, max_seq, nkv, d]`` pytree
(InferenceParams analog, forward_step.py:17-41) threaded through
``lax.scan`` over layers.

Shape policy: programs specialize on (batch, padded max_seq, padded prefill
length, sampling config).  Prefill length is bucketed DOWN and max_seq
bucketed UP to multiples of ``BUCKET`` by the API layer so arbitrary prompt
lengths reuse a small set of compiled programs — numerically identical,
because positions between the bucketed prefill and the true prompt length
are teacher-forced from the prompt (generation.py:211-214 semantics).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.generation.sampling import sample
from megatron_llm_tpu.models.language_model import (
    _compute_dtype,
    make_rope_cache,
    model_forward,
)

BUCKET = 64


class InvalidRequest(ValueError):
    """A request that can never be served as sent (empty prompt, longer
    than the model allows, too many tokens).  The only error the server
    answers with a 400: any other exception out of an engine — a
    lowering, compile or runtime failure — is the server's fault and a
    500 (generation/server.py)."""


# GPT-2 BPE newline conventions used by the reference's stop_on_eol /
# stop_on_double_eol options (generation.py:241-251).
GPT2_EOL = 198
GPT2_DOUBLE_EOL = 628

# compiled-program cache: (config fingerprint, fn name, static arg tuple)
# -> jitted fn.  Keying on the VALUE of the config (not ``id(cfg)``) means
# (a) a config object rebuilt with identical contents — a fresh server
# process section, a test building the same toy config twice — reuses the
# compiled program instead of recompiling, and (b) there is no id-recycling
# hazard: CPython reuses a freed object's id, so an id-keyed cache can serve
# a *different* config's program after the original is GC'd.
_JIT_CACHE: Dict[Tuple, Any] = {}


def config_fingerprint(cfg) -> str:
    """Stable content hash of a Config dataclass tree.

    ``asdict`` flattens the nested dataclasses in deterministic field order;
    repr covers the leaf types configs actually hold (ints, floats, strings,
    bools, None, lists/tuples).  Two configs with equal contents fingerprint
    identically across processes and GC cycles.
    """
    import dataclasses
    import hashlib

    if dataclasses.is_dataclass(cfg):
        payload = repr(dataclasses.asdict(cfg))
    else:  # duck-typed test doubles
        payload = repr(sorted(vars(cfg).items()))
    return hashlib.sha256(payload.encode()).hexdigest()


def cached_jit(cfg, name: str, statics: Tuple, build, **jit_kwargs):
    key = (config_fingerprint(cfg), name, statics)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(build(), **jit_kwargs)
        _JIT_CACHE[key] = fn
    return fn


def clear_jit_cache() -> None:
    """Drop all cached generation programs (frees compiled executables and
    unpins their configs)."""
    _JIT_CACHE.clear()


def init_kv_caches(cfg, batch_size: int, max_seq: int, dtype) -> Tuple[jax.Array, jax.Array]:
    """Pre-allocated stacked KV cache (InferenceParams.key_value_memory_dict
    analog, forward_step.py:17-41); a looped stack's holds a pair a layer
    and pass (models/language_model.py ``looped_forward``)."""
    m = cfg.model
    shape = (m.loop_steps * m.num_layers, batch_size, max_seq,
             m.num_attention_heads_kv, m.kv_channels)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)




def _gather_token_log_probs(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """log_softmax(logits)[..., token] — fp32 (generation.py:71-81)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]


class GenerateResult(NamedTuple):
    tokens: jax.Array            # [b, S] int32, prompt + generations
    lengths: jax.Array           # [b] int32, total generated length incl. prompt
    output_log_probs: jax.Array  # [b, S-1] fp32, logprob of tokens[:, 1:]


class _Carry(NamedTuple):
    context: jax.Array      # position being generated this step
    tokens: jax.Array
    caches: Tuple[jax.Array, jax.Array]
    last_logits: jax.Array
    is_done: jax.Array      # [b] bool
    gen_lengths: jax.Array  # [b] int32
    log_probs: jax.Array
    key: jax.Array


def generate_tokens_fn(
    cfg,
    *,
    prefill_len: int,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
    use_eod_for_termination: bool = True,
    stop_on_double_eol: bool = False,
    stop_on_eol: bool = False,
):
    """Build the one-program analog of
    generate_tokens_probs_and_return_on_first_stage (generation.py:89-285):
    prefill ``prefill_len`` positions, then a while_loop sampling one token
    per step with KV-cached single-position forwards, teacher-forcing
    positions still inside a row's prompt, and terminating early once every
    row has emitted the termination id.

    The returned function has signature
    ``(params, tokens [b,S], lengths [b], samples_length scalar,
       termination_id scalar, sample_key) -> GenerateResult``.
    """
    m = cfg.model

    def run(params, tokens, lengths, samples_length, termination_id, sample_key):
        b, S = tokens.shape
        assert 1 <= prefill_len < S
        rope = make_rope_cache(cfg)
        caches = init_kv_caches(cfg, b, S, _compute_dtype(cfg))

        # --- prefill positions [0, prefill_len) ----------------------------
        prompt = tokens[:, :prefill_len]
        logits, caches = model_forward(
            cfg, params, prompt,
            position_ids=jnp.arange(prefill_len)[None, :].repeat(b, 0),
            rope_cache=rope, kv_caches=caches, cache_index=jnp.int32(0),
        )
        # log-probs of teacher-forced prompt tokens (generation.py:227-239)
        log_probs0 = jnp.zeros((b, S - 1), jnp.float32)
        if prefill_len > 1:
            lp = _gather_token_log_probs(logits[:, :-1], prompt[:, 1:])
            log_probs0 = log_probs0.at[:, : prefill_len - 1].set(lp)
        last_logits = logits[:, -1]  # predicts position prefill_len

        def cond(c: _Carry):
            keep_going = c.context < samples_length
            if use_eod_for_termination:
                keep_going &= ~jnp.all(c.is_done)
            return keep_going

        def body(c: _Carry) -> _Carry:
            key, sub = jax.random.split(c.key)
            new_sample = sample(
                sub, c.last_logits, top_k=top_k, top_p=top_p,
                temperature=temperature, vocab_size=m.vocab_size,
            )
            started = lengths <= c.context  # rows already past their prompt
            prev_col = jax.lax.dynamic_slice_in_dim(
                c.tokens, c.context, 1, axis=1)[:, 0]
            new_col = jnp.where(started, new_sample, prev_col)
            tokens_ = jax.lax.dynamic_update_slice(
                c.tokens, new_col[:, None], (0, c.context)
            )
            # logprob of the token actually placed at `context`
            lp = _gather_token_log_probs(c.last_logits, new_col)
            log_probs_ = jax.lax.dynamic_update_slice(
                c.log_probs, lp[:, None], (0, c.context - 1)
            )
            # termination bookkeeping (generation.py:241-263)
            if stop_on_double_eol:
                prev_tok = jax.lax.dynamic_slice_in_dim(
                    tokens_, c.context - 1, 1, axis=1)[:, 0]
                done_token = ((new_col == GPT2_DOUBLE_EOL)
                              | ((new_col == GPT2_EOL) & (prev_tok == GPT2_EOL))
                              ) & started
            elif stop_on_eol:
                done_token = ((new_col == GPT2_DOUBLE_EOL)
                              | (new_col == GPT2_EOL)) & started
            else:
                done_token = (new_col == termination_id) & started
            just_finished = done_token & ~c.is_done
            gen_lengths_ = jnp.where(just_finished, c.context + 1, c.gen_lengths)
            is_done_ = c.is_done | done_token

            # feed the new token -> logits for position context+1
            logits, caches_ = model_forward(
                cfg, params, new_col[:, None],
                position_ids=jnp.full((b, 1), c.context, jnp.int32),
                rope_cache=rope, kv_caches=c.caches, cache_index=c.context,
            )
            return _Carry(c.context + 1, tokens_, caches_, logits[:, -1],
                          is_done_, gen_lengths_, log_probs_, key)

        init = _Carry(
            jnp.int32(prefill_len), tokens, caches, last_logits,
            jnp.zeros((b,), bool), jnp.full((b,), S, jnp.int32),
            log_probs0, sample_key,
        )
        final = jax.lax.while_loop(cond, body, init)
        gen_lengths = jnp.minimum(final.gen_lengths, samples_length)
        return GenerateResult(final.tokens, gen_lengths, final.log_probs)

    return run


def generate_tokens(cfg, params, tokens, lengths, samples_length, *,
                    prefill_len: int, termination_id, sample_key,
                    top_k: int = 0, top_p: float = 0.0, temperature: float = 1.0,
                    use_eod_for_termination: bool = True,
                    stop_on_double_eol: bool = False,
                    stop_on_eol: bool = False) -> GenerateResult:
    """Compile-cached entry over :func:`generate_tokens_fn`."""
    statics = (prefill_len, top_k, top_p, temperature, use_eod_for_termination,
               stop_on_double_eol, stop_on_eol, tokens.shape)
    fn = cached_jit(cfg, "generate", statics, lambda: generate_tokens_fn(
        cfg, prefill_len=prefill_len, top_k=top_k, top_p=top_p,
        temperature=temperature, use_eod_for_termination=use_eod_for_termination,
        stop_on_double_eol=stop_on_double_eol, stop_on_eol=stop_on_eol,
    ))
    return fn(params, jnp.asarray(tokens, jnp.int32),
              jnp.asarray(lengths, jnp.int32), jnp.asarray(samples_length, jnp.int32),
              jnp.asarray(termination_id, jnp.int32), sample_key)


def score_tokens(cfg, params, tokens: jax.Array) -> jax.Array:
    """score_and_return_on_first_stage analog (generation.py:20-88):
    teacher-forced log-probs of tokens[:, 1:].  Returns [b, s-1] fp32."""
    def build():
        def run(params, tokens):
            b, s = tokens.shape
            logits, _ = model_forward(
                cfg, params, tokens,
                position_ids=jnp.arange(s)[None, :].repeat(b, 0),
                rope_cache=make_rope_cache(cfg),
            )
            return _gather_token_log_probs(logits[:, :-1], tokens[:, 1:])
        return run

    fn = cached_jit(cfg, "score", (tuple(tokens.shape),), build)
    return fn(params, jnp.asarray(tokens, jnp.int32))


# ---------------------------------------------------------------------------
# Beam search
# ---------------------------------------------------------------------------


def _mask_padded_vocab(cfg, logits: jax.Array) -> jax.Array:
    """-inf the vocab-padding region so beams never contain OOV ids (the
    reference leaves padding logits live, generation.py:333)."""
    v = cfg.model.vocab_size
    if v is not None and v < logits.shape[-1]:
        logits = jnp.where(jnp.arange(logits.shape[-1])[None, :] >= v,
                           -jnp.inf, logits)
    return logits


def _beam_prefill(cfg, params, tokens, prefill_len: int):
    """Prefill the beam-size batch (all rows share the same prompt); returns
    next-position log-probs [beam, v] and the caches."""
    beam, S = tokens.shape

    def build():
        def run(params, tokens):
            rope = make_rope_cache(cfg)
            caches = init_kv_caches(cfg, beam, S, _compute_dtype(cfg))
            prompt = tokens[:, :prefill_len]
            logits, caches = model_forward(
                cfg, params, prompt,
                position_ids=jnp.arange(prefill_len)[None, :].repeat(beam, 0),
                rope_cache=rope, kv_caches=caches, cache_index=jnp.int32(0),
            )
            logits = _mask_padded_vocab(cfg, logits[:, -1].astype(jnp.float32))
            return jax.nn.log_softmax(logits, -1), caches
        return run

    return cached_jit(cfg, "beam_prefill", (beam, S, prefill_len), build)(
        params, tokens)


def _beam_step(cfg, params, token_col, context, caches):
    """Feed one token per beam at position ``context``; return next-position
    log-probs [beam, v] and updated caches."""
    beam = token_col.shape[0]

    def build():
        def run(params, token_col, context, caches):
            logits, caches = model_forward(
                cfg, params, token_col[:, None],
                position_ids=jnp.full((beam, 1), context, jnp.int32),
                rope_cache=make_rope_cache(cfg),
                kv_caches=caches, cache_index=context,
            )
            logits = _mask_padded_vocab(cfg, logits[:, -1].astype(jnp.float32))
            return jax.nn.log_softmax(logits, -1), caches
        return run

    return cached_jit(cfg, "beam_step", (beam, caches[0].shape), build)(
        params, token_col, context, caches)


def _beam_topk(cfg, log_probs, scores, first: bool, k: int):
    """Device top-k over the beam*vocab score matrix (the reference's
    torch.topk/sort step, generation.py:335-339) — transfers 2*beam values
    to the host instead of the full [beam, v] matrix."""
    shape = tuple(log_probs.shape)

    def build():
        def run(log_probs, scores):
            new = log_probs + scores[:, None]
            flat = new[0] if first else new.reshape(-1)
            return jax.lax.top_k(flat, k)
        return run

    return cached_jit(cfg, "beam_topk", (shape, first, k), build)(
        log_probs, jnp.asarray(scores, jnp.float32))


def _reorder_beams(cfg, caches, beam_ids):
    """swap_key_value_dict analog (forward_step.py:29-41): reorder the beam
    axis of the stacked caches after beam reranking."""
    fn = cached_jit(cfg, "beam_reorder", (caches[0].shape,),
                    lambda: (lambda c, i: jax.tree.map(lambda a: a[:, i], c)))
    return fn(caches, beam_ids)


def beam_search(
    cfg,
    params,
    tokens,            # [1, S] int array, prompt padded with eod
    prompt_length: int,
    *,
    beam_size: int,
    stop_token: int,
    num_return_gen: int = 1,
    length_penalty: float = 1.0,
    samples_length: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """beam_search_and_return_on_first_stage analog (generation.py:290-417).

    Hypothesis management (the BeamHypotheses heap) is host-side python
    exactly like the reference; the per-token model step and the beam-axis
    cache reorder are jitted device programs.  ``samples_length`` bounds the
    decode horizon (prompt + tokens_to_generate) when ``tokens`` is padded
    wider for compile-cache bucketing.

    The prefill program is compiled at a bucketed length; the remaining
    prompt positions are teacher-forced through the (single, shape-stable)
    per-token step so any prompt length reuses two compiled programs.

    Returns (tokens [num_return_gen, S], scores [num_return_gen]).
    """
    from megatron_llm_tpu.generation.beam_utils import BeamHypotheses

    assert tokens.shape[0] == 1, "beam search supports batch size 1"
    S = int(tokens.shape[1])
    horizon = S if samples_length is None else min(int(samples_length), S)
    if prompt_length >= horizon:
        raise InvalidRequest("context length + tokens_to_generate too large")

    beam_hyp = BeamHypotheses(beam_size, length_penalty)
    tokens = jnp.broadcast_to(jnp.asarray(tokens, jnp.int32), (beam_size, S))
    scores = np.zeros((beam_size,), np.float64)

    # bucketed prefill + teacher-forced catch-up to the true prompt length
    prefill_len = max(1, (prompt_length // BUCKET) * BUCKET)
    log_probs, caches = _beam_prefill(cfg, params, tokens, prefill_len)
    for pos in range(prefill_len, prompt_length):
        log_probs, caches = _beam_step(
            cfg, params, tokens[:, pos], jnp.int32(pos), caches)

    vocab = log_probs.shape[-1]
    tokens_np = np.asarray(tokens)
    done = False
    context_length = prompt_length
    for context_length in range(prompt_length, horizon):
        first = context_length == prompt_length  # beams identical on step 1
        vals, idx = _beam_topk(cfg, log_probs, scores, first, 2 * beam_size)
        order = np.asarray(idx, np.int64)
        best_scores = np.asarray(vals, np.float64)
        best_beam_ids = (np.zeros(2 * beam_size, np.int64) if first
                         else order // vocab)
        best_words = order % vocab

        next_beams = []
        for rank, (token_id, beam_score, beam_id) in enumerate(
            zip(best_words, best_scores, best_beam_ids)
        ):
            if int(token_id) == stop_token:
                if rank < beam_size:  # worse-than-top-beam eos is dropped
                    beam_hyp.add(
                        tokens_np[beam_id].copy(), float(beam_score),
                        context_length + 1 - prompt_length,
                    )
            else:
                next_beams.append((int(token_id), float(beam_score), int(beam_id)))
            if len(next_beams) == beam_size:
                break

        if beam_hyp.is_done(float(best_scores.max()),
                            context_length + 1 - prompt_length):
            done = True
            break

        best_batches = np.array([nb[2] for nb in next_beams], np.int64)
        tokens_np = tokens_np[best_batches]
        tokens_np[:, context_length] = [nb[0] for nb in next_beams]
        scores = np.array([nb[1] for nb in next_beams], np.float64)

        if context_length == horizon - 1:
            break
        caches = _reorder_beams(cfg, caches, jnp.asarray(best_batches))
        log_probs, caches = _beam_step(
            cfg, params,
            jnp.asarray(tokens_np[:, context_length], jnp.int32),
            jnp.int32(context_length), caches,
        )

    if not done:
        for beam_id in range(beam_size):
            beam_hyp.add(tokens_np[beam_id].copy(), float(scores[beam_id]),
                         context_length + 1 - prompt_length)

    sorted_hyps = sorted(beam_hyp.beams, key=lambda x: x[0], reverse=True)
    num_return_gen = min(num_return_gen, len(sorted_hyps))
    out_scores = jnp.asarray([sorted_hyps[i][0] for i in range(num_return_gen)])
    out_tokens = jnp.asarray(
        np.stack([sorted_hyps[i][1] for i in range(num_return_gen)])
    )
    return out_tokens, out_scores
