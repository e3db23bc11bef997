"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; the linear layers
of Qwen3-Next and GigaChat3.5): linear attention whose state is corrected
before it is added to, so a sequence's whole past is a square state of
constant size a value head and no key or value is kept.

For one value head, with ``g_t <= 0`` the log decay, ``beta_t`` in (0, 1)
the write strength, ``q_t``, ``k_t`` of ``dk`` values (L2-normalised, the
query scaled by ``dk^-0.5``: the caller's) and ``v_t`` of ``dv``::

    S <- exp(g_t) S;   S <- S + k_t (beta_t (v_t - S^T k_t))^T;   o_t = S^T q_t

with ``S [dk, dv]``.  Unlike power retention (ops/retention.py), which ADDS
a decayed outer product, the rule reads the state back before it writes.
Four forms of the same numbers:

* **recurrent** (:func:`delta_step`, :func:`delta_recurrent`): the lines
  above, a token at a time; what the plain reference computes;
* **attention** (:func:`delta_attention`): quadratic over the whole
  sequence from a zero state, ``o = (Q K^T . L) T (beta V)`` with ``T`` the
  inverse of a unit lower-triangular matrix (the WY representation);
* **chunked** (:func:`delta_chunk`, :func:`delta_chunked`): runs of
  ``CHUNK`` rows, each reading the state once, solving its own triangle
  and writing the state once; the dense forward and, by autodiff, a
  trainer;
* **the tick's** (:func:`delta_tick`): ragged rows against a pool of
  per-slot states, a run of rows (``ops/retention.tick_runs``) reading its
  slot's state once and writing it once.  ``jnp`` throughout: the fallback
  off the TPU and what the tests hold the kernel
  (ops/pallas/gated_delta.py) to.

A key head serves ``hv / hk`` consecutive value heads.  The state is
float32 whatever the activations are, as is the 3-row tail of the causal
depthwise convolution (:func:`causal_conv`, :func:`conv_tick`) that feeds
q, k and v: both are what a sequence keeps (:class:`DeltaState`).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.ops.retention import tick_runs

CHUNK = 64           # rows of one run of the chunked form
PUT_ROWS = 128       # rows of the tail pool one step of the tick's write takes
F32 = jnp.float32


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _per_value_head(t: jax.Array, hv: int) -> jax.Array:
    """[..., hk, d] -> [..., hv, d]: a key head's value heads."""
    return jnp.repeat(t, hv // t.shape[-2], axis=-2)


class DeltaState(NamedTuple):
    """What a sequence keeps of a gated-delta layer, float32: ``s`` ``[...,
    hv, dk, dv]`` and ``conv`` ``[rows, (width - 1) * channels]``, the last
    inputs of the causal convolution, oldest first and side by side in ONE
    row a sequence: of a pool ``[layers, slots]`` row ``layer * slots +
    slot`` (a leaf ``[layers, slots, ...]`` lives on a TPU with its few
    layers on the sublanes or its row in (8, 128) tiles of its own, and
    every tick would lay the whole pool out anew to gather a slot's
    row)."""

    s: jax.Array
    conv: jax.Array


def zero_state(lead: Tuple[int, ...], hv: int, dk: int, dv: int,
               conv_width: int, channels: int) -> DeltaState:
    return DeltaState(jnp.zeros((*lead, hv, dk, dv), F32),
                      jnp.zeros((math.prod(lead),
                                 (conv_width - 1) * channels), F32))


# ---------------------------------------------------------------------------
# The causal depthwise convolution
# ---------------------------------------------------------------------------


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x [b, s, c]``, ``w [width, c]`` (``w[-1]`` multiplies the current
    input): ``y_t = sum_j w[width - 1 - j] x_{t - j}``, zeros before the
    sequence, summed from the current input back as :func:`conv_tick`
    sums.  float32."""
    width = w.shape[0]
    x = x.astype(F32)
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(pad[:, j:j + s] * w[j].astype(F32) for j in range(width)[::-1])


def conv_tick(x: jax.Array, w: jax.Array, tails: jax.Array, slots, positions,
              base=0):
    """The tick's rows of the convolution.  ``x [R, c]``; ``tails`` the
    pool of each slot's last inputs, a row a layer and slot
    (:class:`DeltaState`), ``base`` the row of this layer's slot 0.  A row takes
    what its run's earlier rows fed and, behind the run's start, its
    slot's tail (zeros where the run starts a sequence); a run's last row
    leaves the slot its new tail, and no other row writes anything: rows
    of the pool that no live run names keep their bits, the layer's null
    row (``base``: what a dead row names, which nothing reads) among them.
    The engine hands a slot one run a tick (a sequence is decoding, one
    row, or filling, whose chunks of one tick are consecutive rows at
    consecutive positions: ``generation/engine.py`` ``_plan_ragged_prefill``
    and the decode rows before them); of two runs that named one slot the
    LATER would leave its tail.  Returns (y [R, c] float32, the pool)."""
    r, c = x.shape
    back = w.shape[0] - 1
    x = x.astype(F32)
    live, first, fresh = tick_runs(slots, positions)
    rows = jnp.arange(r)
    start = jax.lax.cummax(jnp.where(first, rows, -1))     # the run's first
    at = rows - start                                       # place in the run
    fresh_run = fresh[jnp.maximum(start, 0)]
    tail = jnp.where((live & ~fresh_run)[:, None],
                     tails[base + slots], 0.0)              # [R, back * c]
    # the input ``j`` rows back: a row of the run, or the tail's
    prev = []
    for j in range(1, back + 1):
        kept = sum(jnp.where((back - j + at == i)[:, None],
                             tail[:, i * c:(i + 1) * c], 0.0)
                   for i in range(back - j, back))
        prev.append(jnp.where((at >= j)[:, None],
                              x[jnp.maximum(rows - j, 0)], kept))
    y = x * w[back].astype(F32)
    for j, p in enumerate(prev, start=1):
        y = y + p * w[back - j].astype(F32)
    # a run's last row: the row after it is not its run's
    goes_on = jnp.concatenate([live[1:] & ~first[1:], jnp.zeros((1,), bool)])
    new_tail = jnp.concatenate(prev[:back - 1][::-1] + [x], axis=1)
    to = jnp.where(live & ~goes_on, base + slots, -1)       # else: nowhere
    return jnp.where(live[:, None], y, 0.0), _put_rows(
        tails, new_tail.astype(tails.dtype), to)


def _put_rows(pool: jax.Array, new: jax.Array, to: jax.Array) -> jax.Array:
    """``pool [n, d]`` with row ``to[i]`` set to ``new[i]`` wherever ``to[i]
    >= 0``, the later of two rows that name one; every other row keeps its
    bits.  Turned round into gathers: the pool's rows from the lowest named
    to the highest, ``PUT_ROWS`` at a time, each looking up the row of
    ``new`` that names it.  ``pool.at[to].set(new)`` says the same, but a
    scatter of R rows of d values reaches a TPU as a loop of R
    dynamic-update-slices, one row each, whatever it is told of its indices
    (unique, sorted, dropped), and the loop takes twenty times what the
    bytes need."""
    n, r = pool.shape[0], to.shape[0]
    b = min(PUT_ROWS, n)
    rows = jnp.arange(r, dtype=jnp.int32)
    lowest = jnp.min(jnp.where(to >= 0, to, n))
    highest = jnp.max(to)                                   # -1: none named

    def block(carry):
        at, pool = carry
        at0 = jnp.minimum(at, n - b)            # the last block ends the pool
        here = at0 + jnp.arange(b, dtype=to.dtype)
        src = jnp.max(jnp.where(to[None, :] == here[:, None], rows[None, :],
                                -1), axis=1)                # [b]; -1: keep
        old = jax.lax.dynamic_slice_in_dim(pool, at0, b)
        put = jnp.where((src >= 0)[:, None], new[jnp.maximum(src, 0)], old)
        return at + b, jax.lax.dynamic_update_slice_in_dim(pool, put, at0, 0)

    return jax.lax.while_loop(lambda carry: carry[0] <= highest, block,
                              (lowest, pool))[1]


# ---------------------------------------------------------------------------
# The forms over dense [b, s] sequences
# ---------------------------------------------------------------------------


def delta_step(s: jax.Array, q, k, v, g, beta):
    """One token of the recurrent form.  ``s [b, hv, dk, dv]``; q, k ``[b,
    hk, dk]``; v ``[b, hv, dv]``; g, beta ``[b, hv]``.  Returns (o ``[b, hv,
    dv]``, the new state)."""
    hv = v.shape[-2]
    q, k = (_per_value_head(t.astype(F32), hv) for t in (q, k))
    v = v.astype(F32)
    s = s * jnp.exp(g.astype(F32))[..., None, None]
    read = jnp.einsum("bhkv,bhk->bhv", s, k, precision="highest")
    u = beta.astype(F32)[..., None] * (v - read)
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision="highest"), s


def delta_recurrent(q, k, v, g, beta, s0=None):
    """The recurrent form over ``[b, s]``: a scan of :func:`delta_step`.
    q, k ``[b, s, hk, dk]``; v ``[b, s, hv, dv]``; g, beta ``[b, s, hv]``.
    Returns (o ``[b, s, hv, dv]`` float32, the last state)."""
    b, _, hv, dv = v.shape
    if s0 is None:
        s0 = jnp.zeros((b, hv, q.shape[-1], dv), F32)

    def one(s, xs):
        o, s = delta_step(s, *xs)
        return s, o

    s, os_ = jax.lax.scan(
        one, s0, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(os_, 0, 1), s


def delta_chunk(s: jax.Array, q, k, v, g, beta):
    """One run of rows against the state before it (the WY form).  Shapes
    as :func:`delta_recurrent` with ``s`` the run's rows.  Returns (o, the
    state after the run)."""
    hv = v.shape[2]
    q, k = (_per_value_head(t.astype(F32), hv).transpose(0, 2, 1, 3)
            for t in (q, k))                                   # [b,h,r,dk]
    v = v.astype(F32).transpose(0, 2, 1, 3)                    # [b,h,r,dv]
    beta = beta.astype(F32).transpose(0, 2, 1)[..., None]      # [b,h,r,1]
    big_g = jnp.cumsum(g.astype(F32), axis=1).transpose(0, 2, 1)  # [b,h,r]
    r = q.shape[2]
    seen = jnp.tril(jnp.ones((r, r), bool))
    gap = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
    hi = dict(precision="highest")
    kb = k * beta
    with jax.named_scope("intra_run"):
        # (I + strict_tril(K_beta K^T . L)) T = I: the run's own triangle
        tri = jnp.einsum("bhtk,bhjk->bhtj", kb, k, **hi) * decay
        tri = jnp.where(jnp.tril(jnp.ones((r, r), bool), -1), tri, 0.0) \
            + jnp.eye(r, dtype=F32)
        rhs = jnp.concatenate(
            [kb * jnp.exp(big_g)[..., None], v * beta], axis=-1)
        sol = jax.scipy.linalg.solve_triangular(
            tri, rhs, lower=True, unit_diagonal=True)
        w, u = sol[..., :k.shape[-1]], sol[..., k.shape[-1]:]
    with jax.named_scope("state_query"):
        v_new = u - jnp.einsum("bhtk,bhkv->bhtv", w, s, **hi)
        o = jnp.einsum("bhtk,bhkv->bhtv", q * jnp.exp(big_g)[..., None], s,
                       **hi)
        o = o + jnp.einsum(
            "bhtj,bhjv->bhtv",
            jnp.einsum("bhtk,bhjk->bhtj", q, k, **hi) * decay, v_new, **hi)
    with jax.named_scope("state_update"):
        to_end = jnp.exp(big_g[..., -1:] - big_g)[..., None]
        s = s * jnp.exp(big_g[..., -1])[..., None, None] + jnp.einsum(
            "bhtk,bhtv->bhkv", k * to_end, v_new, **hi)
    return o.transpose(0, 2, 1, 3), s


def delta_attention(q, k, v, g, beta):
    """The attention form: the whole sequence as one run from a zero
    state, quadratic in its length."""
    b, _, hv, dv = v.shape
    return delta_chunk(jnp.zeros((b, hv, q.shape[-1], dv), F32),
                       q, k, v, g, beta)[0]


def delta_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form over ``[b, s]`` from a zero state: a scan of
    :func:`delta_chunk` over runs of ``chunk`` rows.  A padded tail carries
    zero keys, ``beta`` 0 and no decay, so it leaves the state as it was;
    its outputs are dropped."""
    b, s, hv, dv = v.shape
    chunk = min(chunk, s)
    runs = -(-s // chunk)
    pad = runs * chunk - s

    def cut(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(b, runs, chunk, *t.shape[2:]), 1, 0)

    def one(state, xs):
        o, state = delta_chunk(state, *xs)
        return state, o

    _, os_ = jax.lax.scan(one, jnp.zeros((b, hv, q.shape[-1], dv), F32),
                          tuple(cut(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(os_, 0, 1).reshape(b, runs * chunk, hv, dv)[:, :s]


# ---------------------------------------------------------------------------
# The tick: ragged rows against a pool of per-slot states
# ---------------------------------------------------------------------------


def delta_tick(q, k, v, g, beta, pool: jax.Array, slots, positions,
               layer=None):
    """The tick's rows, ``jnp`` throughout.  q, k ``[R, hk, dk]``; v ``[R,
    hv, dv]``; g, beta ``[R, hv]``; ``pool`` ``[(layers,) slots + 1, hv, dk,
    dv]`` (``layer``: which of its layers); ``slots`` [R] each row's state
    slot (0: a dead row, which touches no state), ``positions`` [R].  Rows
    of one run are consecutive: the rows are walked in order, each on its
    slot's state, which a run at position 0 takes as zero whatever the
    slot held.  Returns (o ``[R, hv, dv]`` float32, the pool)."""
    live, _, fresh = tick_runs(slots, positions)
    lead = () if layer is None else (layer,)

    def one(pool, xs):
        q_t, k_t, v_t, g_t, b_t, slot, alive, zero = xs
        at = jnp.where(alive, slot, 0)
        s = jnp.where(zero, 0.0, pool[(*lead, at)])
        o, s = delta_step(s[None], q_t[None], k_t[None], v_t[None],
                          g_t[None], b_t[None])
        # a dead row rewrites the null slot with what it held
        s = jnp.where(alive, s[0], pool[(*lead, at)])
        return pool.at[(*lead, at)].set(s), jnp.where(alive, o[0], 0.0)

    pool, o = jax.lax.scan(
        one, pool, (q, k, v, g, beta, slots, live, fresh))
    return o, pool


# ---------------------------------------------------------------------------
# A state that is a conv tail alone (a gated short convolution: LFM2)
# ---------------------------------------------------------------------------


class ConvTail(NamedTuple):
    """What a sequence keeps of a gated short-convolution layer: ``conv``
    ``[layers * (slots + 1), (width - 1) * channels]``, the conv's last
    inputs as :class:`DeltaState` lays them out, and nothing else (no
    recurrent ``s``).  Its dtype is the activations': the conv's inputs are
    rounded to it before they are kept or convolved, so the tail holds them
    exactly."""

    conv: jax.Array


def zero_tails(lead: Tuple[int, ...], conv_width: int, channels: int,
               dtype) -> ConvTail:
    return ConvTail(jnp.zeros((math.prod(lead),
                               (conv_width - 1) * channels), dtype))
