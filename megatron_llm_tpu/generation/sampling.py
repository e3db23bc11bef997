"""Sampling utilities — functional JAX analog of
megatron/text_generation/sampling.py (sample:45, top-k filter:14, top-p
filter:22).

All functions are pure and jit-safe with *static* top_k/top_p/temperature
(the jit cache is keyed per sampling config; a config change recompiles
once, which matches how a generation server runs in practice).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e10


def modify_logits_for_top_k_filtering(logits: jax.Array, top_k: int) -> jax.Array:
    """Keep only the top-k logits, set the rest to -inf (sampling.py:14-18)."""
    kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def modify_logits_for_top_p_filtering(logits: jax.Array, top_p: float) -> jax.Array:
    """Nucleus filtering (sampling.py:22-41), including the reference's
    shift-by-one so the first token crossing the threshold is kept."""
    sorted_idx = jnp.argsort(logits, axis=-1)[..., ::-1]
    sorted_logits = jnp.take_along_axis(logits, sorted_idx, axis=-1)
    cum_probs = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
    filter_sorted = cum_probs > top_p
    # shift right: token at the boundary stays selectable
    filter_sorted = jnp.concatenate(
        [jnp.zeros_like(filter_sorted[..., :1]), filter_sorted[..., :-1]], axis=-1
    )
    # un-sort the filter back to vocab order
    inv = jnp.argsort(sorted_idx, axis=-1)
    filter_ = jnp.take_along_axis(filter_sorted, inv, axis=-1)
    return jnp.where(filter_, NEG_INF, logits)


def sample(
    key: Optional[jax.Array],
    logits: jax.Array,  # [b, v]
    *,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
    vocab_size: Optional[int] = None,
) -> jax.Array:
    """Sample one token per row (sampling.py:45-95). ``top_k == 1`` is greedy;
    top-k and top-p are mutually exclusive.

    ``vocab_size`` masks the vocab-padding region to -inf before selection.
    (The reference instead CLAMPS the sample into [0, vocab) after selection,
    sampling.py:90-93 — which can spuriously emit token vocab-1 whenever a
    padding logit wins; masking picks the best *valid* token instead.)"""
    assert logits.ndim == 2, "expected [b, v] logits"
    if vocab_size and vocab_size < logits.shape[-1]:
        logits = jnp.where(
            jnp.arange(logits.shape[-1])[None, :] >= vocab_size, NEG_INF, logits
        )
    if top_k == 1:
        assert top_p == 0.0, "cannot set both greedy and top-p sampling"
        samples = jnp.argmax(logits, axis=-1)
    else:
        logits = logits.astype(jnp.float32)
        if temperature != 1.0:
            logits = logits / temperature
        if top_k > 1:
            assert top_p == 0.0, "cannot set both top-k and top-p sampling"
            assert top_k <= logits.shape[-1], "top-k larger than logit size"
            logits = modify_logits_for_top_k_filtering(logits, top_k)
        elif top_p > 0.0:
            assert top_p <= 1.0, "top-p should be in (0, 1]"
            logits = modify_logits_for_top_p_filtering(logits, top_p)
        assert key is not None, "non-greedy sampling needs a PRNG key"
        samples = jax.random.categorical(key, logits, axis=-1)
    return samples.astype(jnp.int32)


def filtered_logits_per_slot(
    logits: jax.Array,       # [b, v]
    *,
    top_k: jax.Array,        # [b] int32 (0 = off, 1 = greedy, >1 = filter)
    top_p: jax.Array,        # [b] fp32  (0 = off; ignored where top_k acts)
    temperature: jax.Array,  # [b] fp32  (ignored for greedy rows)
    vocab_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The per-row filter pipeline :func:`sample_per_slot` samples from.

    Returns ``(filtered, greedy)``: the vocab-masked, temperature-scaled,
    top-k/top-p-filtered fp32 logits [b, v] (softmax of a row is exactly
    the categorical distribution a non-greedy slot draws from) and the
    greedy argmax [b] over the vocab-masked RAW logits (no temperature —
    :func:`sample`'s greedy branch).  The speculative-decoding verify step
    (generation/speculative/verify.py) consumes both: draft/target
    distributions for residual rejection sampling must be the SAME
    distributions the non-speculative tick samples from, or acceptance
    stops being lossless.
    """
    assert logits.ndim == 2, "expected [b, v] logits"
    b, v = logits.shape
    if vocab_size and vocab_size < v:
        logits = jnp.where(jnp.arange(v)[None, :] >= vocab_size, NEG_INF, logits)
    greedy = jnp.argmax(logits, axis=-1)

    l32 = logits.astype(jnp.float32)
    safe_temp = jnp.where(temperature > 0, temperature, 1.0).astype(jnp.float32)
    l32 = l32 / safe_temp[:, None]

    def apply_filters(x):
        # one descending sort serves both filters
        sorted_idx = jnp.argsort(x, axis=-1)[..., ::-1]
        sorted_logits = jnp.take_along_axis(x, sorted_idx, axis=-1)

        # dynamic top-k: keep values >= the row's k-th largest
        kth = jnp.take_along_axis(
            sorted_logits, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=-1)
        l_topk = jnp.where(x < kth, NEG_INF, x)

        # dynamic top-p with the shift-by-one boundary convention of
        # modify_logits_for_top_p_filtering
        cum_probs = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        filter_sorted = cum_probs > top_p[:, None]
        filter_sorted = jnp.concatenate(
            [jnp.zeros_like(filter_sorted[..., :1]), filter_sorted[..., :-1]],
            axis=-1)
        inv = jnp.argsort(sorted_idx, axis=-1)
        filter_ = jnp.take_along_axis(filter_sorted, inv, axis=-1)
        l_topp = jnp.where(filter_, NEG_INF, x)

        use_k = (top_k > 1)[:, None]
        use_p = (top_p > 0)[:, None] & ~use_k
        return jnp.where(use_k, l_topk, jnp.where(use_p, l_topp, x))

    # all-greedy / pure-temperature ticks skip the two vocab sorts entirely
    # (the common serving mix; greedy decode bench ticks hit this branch)
    filtered = jax.lax.cond(
        jnp.any((top_k > 1) | (top_p > 0)), apply_filters, lambda x: x, l32)
    return filtered, greedy


def sample_per_slot(
    keys: jax.Array,         # [b, 2] uint32 — one PRNG key per row
    logits: jax.Array,       # [b, v]
    *,
    top_k: jax.Array,        # [b] int32 (0 = off, 1 = greedy, >1 = filter)
    top_p: jax.Array,        # [b] fp32  (0 = off; ignored where top_k acts)
    temperature: jax.Array,  # [b] fp32  (ignored for greedy rows)
    vocab_size: Optional[int] = None,
) -> jax.Array:
    """One batched sampling step with *per-row* sampling params and keys.

    The continuous-batching engine decodes many requests in one tick, each
    with its own (temperature, top_k, top_p) — so unlike :func:`sample`,
    where the config is static and baked into the compiled program, here the
    params are traced arrays and one program serves every mix.  Per-row keys
    keep each request's sample stream a function of (its seed, its step
    index) alone — independent of which slot it landed in or which other
    requests share the tick.  Greedy rows (``top_k == 1``) reproduce
    :func:`sample`'s greedy branch exactly: argmax over the vocab-masked
    logits, no temperature.

    Returns [b] int32 token ids.
    """
    filtered, greedy = filtered_logits_per_slot(
        logits, top_k=top_k, top_p=top_p, temperature=temperature,
        vocab_size=vocab_size)
    sampled = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
        keys, filtered)
    return jnp.where(top_k == 1, greedy, sampled).astype(jnp.int32)


def sample_with_log_prob(
    keys: jax.Array,         # [b, 2] uint32
    logits: jax.Array,       # [b, v]
    *,
    top_k: jax.Array,
    top_p: jax.Array,
    temperature: jax.Array,
    vocab_size: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`sample_per_slot` and the sample's log-probability under the
    RAW logits in float32 (what a stream reports; its exponential is the
    CONFIDENCE a block model's unmasking ranks positions by,
    generation/blocks.py).  Returns ([b] int32, [b] float32)."""
    tok = sample_per_slot(keys, logits, top_k=top_k, top_p=top_p,
                          temperature=temperature, vocab_size=vocab_size)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
