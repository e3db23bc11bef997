"""Mamba-2's selective state space (SSD, arXiv:2405.21060; the ``M`` layers
of NVIDIA's Nemotron-H family): a sequence's whole past is one state ``S
[p, n]`` a head (``p`` the head's width, ``n`` the state size), decayed by
a scalar a head and added to, so no key or value is kept.

For one head, with ``dt_t > 0`` the step (``softplus(dt_t + dt_bias)``: the
caller's, :func:`discretize`; no clamp), ``A < 0`` the head's rate
(``-exp(A_log)``), ``x_t`` of ``p`` values and ``B_t``, ``C_t`` of ``n``
values shared by the heads of a GROUP (``n_groups``: head ``h`` of ``H``
reads group ``h // (H / groups)``)::

    S <- exp(dt_t A) S + dt_t x_t B_t^T;    y_t = S C_t + D x_t

It ADDS a decayed outer product, as power retention does
(ops/retention.py) and the gated delta rule does not (ops/gated_delta.py),
with no feature map and no normaliser.  What follows the state space is
the layer's own norm (:func:`gated_group_norm`: Mamba-2's gated RMSNorm
with ``norm_before_gate`` false, over each group's values).  Three forms
of the same numbers:

* **recurrent** (:func:`mamba_step`, :func:`mamba_recurrent`): the lines
  above, a token at a time; what the plain reference computes;
* **chunked** (:func:`mamba_chunk`, :func:`mamba_chunked`): SSD.  Inside a
  run of ``CHUNK`` rows the masked ``C B^T`` product on ``dt x``, between
  runs the carried state, the decays as cumulative sums of ``dt A`` in
  float32; the dense forward and, by autodiff, a trainer;
* **the tick's** (:func:`mamba_tick`): ragged rows against a pool of
  per-slot states, every run of rows (``ops/retention.tick_runs``) in the
  chunked form at once: it reads its slot's state once and writes it once,
  and a run at position 0 starts from zero whatever the slot held.  ``jnp``
  throughout: the fallback off the TPU and what the tests hold the kernel
  (ops/pallas/mamba2.py) to.  The kernel takes a run in the same form a
  TILE of ``SWEEP_TILE`` rows at a time on the state it holds in VMEM
  (until PR 54 it walked a run's rows, a pass over the state each):
  :func:`sweep_steps` counts its passes on the host.

The state is float32 whatever the activations are, as is the tail of the
causal depthwise convolution that feeds x, B and C
(``ops/gated_delta.causal_conv`` / ``conv_tick``): both are what a sequence
keeps (:class:`MambaState`).  It is STORED with the state size on the
sublanes and heads x width on the lanes, ``s [..., n, h * p]``: the layout
the tick's kernel reads and writes in place (a head of 64 alone would fill
half a lane row).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.ops.retention import tick_runs

CHUNK = 128          # rows of one run of the chunked form (``chunk_size``)
SWEEP_TILE = 32      # rows of one tile of the tick's sweep (the kernel's)
F32 = jnp.float32


class MambaState(NamedTuple):
    """What a sequence keeps of a Mamba-2 layer, float32: ``s`` ``[..., n,
    h * p]`` and ``conv`` ``[rows, (width - 1) * channels]``, the last
    inputs of the causal convolution in ONE row a sequence (of a pool
    ``[layers, slots]`` row ``layer * slots + slot``, as
    ``ops/gated_delta.DeltaState`` keeps them and for its reason)."""

    s: jax.Array
    conv: jax.Array


def zero_state(lead: Tuple[int, ...], heads: int, width: int, n: int,
               conv_width: int, channels: int) -> MambaState:
    return MambaState(jnp.zeros((*lead, n, heads * width), F32),
                      jnp.zeros((math.prod(lead),
                                 (conv_width - 1) * channels), F32))


def discretize(dt_raw: jax.Array, dt_bias: jax.Array, a_log: jax.Array):
    """``(dt, log_decay)`` ``[..., h]`` float32: the step ``softplus(dt_raw
    + dt_bias)`` (``time_step_limit`` is ``(0, inf)``: no clamp) and ``dt
    A`` with ``A = -exp(A_log)``, the log of what a token leaves of the
    state."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    return dt, -jnp.exp(a_log.astype(F32)) * dt


def gated_group_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """``RMSNorm(y * SiLU(z)) * scale`` with the mean square taken over each
    of the ``groups`` consecutive blocks of the last axis (Mamba-2's
    ``RMSNormGated`` with ``norm_before_gate`` false and ``group_size`` =
    width / ``n_groups``), float32.  ``y``, ``z`` ``[..., width]``."""
    y = y.astype(F32) * jax.nn.silu(z.astype(F32))
    blocks = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    blocks = blocks * jax.lax.rsqrt(
        jnp.mean(blocks * blocks, axis=-1, keepdims=True) + eps)
    return blocks.reshape(y.shape) * scale.astype(F32)


def _per_head(t: jax.Array, heads: int) -> jax.Array:
    """[..., g, n] -> [..., h, n]: a group's heads."""
    return jnp.repeat(t.astype(F32), heads // t.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# The forms over dense [b, s] sequences
# ---------------------------------------------------------------------------


def mamba_step(s: jax.Array, x, dt, log_decay, b, c):
    """One token of the recurrent form.  ``s [bt, n, h, p]``; x ``[bt, h,
    p]``; dt, log_decay ``[bt, h]``; b, c ``[bt, g, n]``.  Returns (y ``[bt,
    h, p]`` without the skip, the new state)."""
    h = x.shape[-2]
    b, c = _per_head(b, h), _per_head(c, h)                     # [bt, h, n]
    dtx = dt.astype(F32)[..., None] * x.astype(F32)
    s = s * jnp.exp(log_decay.astype(F32))[:, None, :, None] \
        + b.transpose(0, 2, 1)[..., None] * dtx[:, None]
    return jnp.einsum("bnhp,bhn->bhp", s, c, precision="highest"), s


def mamba_recurrent(x, dt, log_decay, b, c, s0=None):
    """The recurrent form over ``[bt, s]``: a scan of :func:`mamba_step`.
    x ``[bt, s, h, p]``; dt, log_decay ``[bt, s, h]``; b, c ``[bt, s, g,
    n]``.  Returns (y ``[bt, s, h, p]`` float32, the last state)."""
    bt, _, h, p = x.shape
    if s0 is None:
        s0 = jnp.zeros((bt, b.shape[-1], h, p), F32)

    def one(s, xs):
        y, s = mamba_step(s, *xs)
        return s, y

    s, ys = jax.lax.scan(one, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, log_decay, b, c)))
    return jnp.moveaxis(ys, 0, 1), s


def mamba_chunk(s: jax.Array, x, dt, log_decay, b, c):
    """One run of rows against the state before it (SSD).  Shapes as
    :func:`mamba_recurrent` with ``s`` the run's rows.  Returns (y, the
    state after the run)."""
    h = x.shape[2]
    r = x.shape[1]
    hi = dict(precision="highest")
    dtx = dt.astype(F32)[..., None] * x.astype(F32)            # [bt,r,h,p]
    b, c = _per_head(b, h), _per_head(c, h)                    # [bt,r,h,n]
    big_l = jnp.cumsum(log_decay.astype(F32), axis=1).transpose(0, 2, 1)
    with jax.named_scope("state_query"):
        y = jnp.einsum("bthn,bnhp->bthp",
                       c * jnp.exp(big_l).transpose(0, 2, 1)[..., None],
                       s, **hi)
    with jax.named_scope("intra_run"):
        seen = jnp.tril(jnp.ones((r, r), bool))
        gap = big_l[..., :, None] - big_l[..., None, :]        # [bt,h,t,j]
        w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0) \
            * jnp.einsum("bthn,bjhn->bhtj", c, b, **hi)
        y = y + jnp.einsum("bhtj,bjhp->bthp", w, dtx, **hi)
    with jax.named_scope("state_update"):
        to_end = jnp.exp(big_l[..., -1:] - big_l).transpose(0, 2, 1)
        s = s * jnp.exp(big_l[..., -1])[:, None, :, None] + jnp.einsum(
            "bjhn,bjhp->bnhp", b * to_end[..., None], dtx, **hi)
    return y, s


def mamba_chunked(x, dt, log_decay, b, c, s0=None, chunk: int = CHUNK):
    """The chunked form over ``[bt, s]`` (from a zero state unless ``s0``):
    a scan of :func:`mamba_chunk` over runs of ``chunk`` rows.  A padded
    tail carries a zero step and no decay, so it leaves the state as it
    was; its outputs are dropped.  Returns (y, the last state), as
    :func:`mamba_recurrent`."""
    bt, s, h, p = x.shape
    chunk = min(chunk, s)
    runs = -(-s // chunk)
    pad = runs * chunk - s
    if s0 is None:
        s0 = jnp.zeros((bt, b.shape[-1], h, p), F32)

    def cut(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(bt, runs, chunk, *t.shape[2:]), 1, 0)

    def one(state, xs):
        y, state = mamba_chunk(state, *xs)
        return state, y

    state, ys = jax.lax.scan(
        one, s0, tuple(cut(t) for t in (x, dt, log_decay, b, c)))
    return jnp.moveaxis(ys, 0, 1).reshape(bt, runs * chunk, h, p)[:, :s], state


# ---------------------------------------------------------------------------
# The tick: ragged rows against a pool of per-slot states
# ---------------------------------------------------------------------------


def sweep_steps(slots, positions) -> int:
    """Passes over a state the tick's sweep makes a layer and block of
    heads, on the host (numpy) by the kernel's own rule
    (``ops/pallas/mamba2.sweep_plan``): one a SEGMENT, the consecutive rows
    of one run inside one tile of ``SWEEP_TILE`` rows of the tick's row
    axis.  A decode row is one; a prompt run of k rows that starts on a
    tile's first row ``ceil(k / SWEEP_TILE)``, one more where it starts
    inside a tile."""
    slots, positions = np.asarray(slots), np.asarray(positions)
    live = slots > 0
    goes_on = np.zeros(live.shape, bool)
    goes_on[1:] = (live[1:] & (slots[1:] == slots[:-1])
                   & (positions[1:] == positions[:-1] + 1))
    goes_on &= np.arange(live.size) % SWEEP_TILE != 0
    return int((live & ~goes_on).sum())


def mamba_tick(x, dt, log_decay, b, c, pool: jax.Array, slots, positions,
               layer=None):
    """The tick's rows in the chunked form, ``jnp`` throughout.  x ``[R, h,
    p]``; dt, log_decay ``[R, h]``; b, c ``[R, g, n]``; ``pool`` ``[(layers,)
    slots + 1, n, h * p]`` (``layer``: which of its layers); ``slots`` [R]
    each row's state slot (0: a dead row, which touches no state),
    ``positions`` [R].  Rows of one run (``tick_runs``) are consecutive: a
    prompt run is one masked ``C B^T`` product, not a walk of its rows.
    Returns (y ``[R, h, p]`` float32 without the skip, the pool)."""
    r, h, p = x.shape
    n = b.shape[-1]
    hi = dict(precision="highest")
    live, first, fresh = tick_runs(slots, positions)
    run = jnp.cumsum(first) * live                  # 0: dead; runs from 1
    same = (run[:, None] == run[None, :]) & live[:, None]
    seen = same & (jnp.arange(r)[:, None] >= jnp.arange(r)[None, :])
    ld = jnp.where(live[:, None], log_decay.astype(F32), 0.0)
    # the decays of the run's rows up to and with t, and of the whole run
    # (float32 sums: a TPU's default matmul would round them to bfloat16)
    big_l = jnp.einsum("tj,jh->th", seen.astype(F32), ld, **hi)    # [R,h]
    run_end = jnp.einsum("tj,jh->th", same.astype(F32), ld, **hi)
    dtx = jnp.where(live[:, None, None],
                    dt.astype(F32)[..., None] * x.astype(F32), 0.0)
    b, c = _per_head(b, h), _per_head(c, h)                        # [R,h,n]
    lead = () if layer is None else (layer,)
    states = pool[lead].reshape(-1, n, h, p)
    # the run's state before it: zero for a fresh run
    fresh_run = (same & fresh[None, :]).any(axis=1)
    with jax.named_scope("state_query"):
        s0 = jnp.where((~fresh_run & live)[:, None, None, None],
                       states[slots], 0.0)                     # [R,n,h,p]
        y = jnp.einsum("thn,tnhp->thp", c * jnp.exp(big_l)[..., None], s0,
                       **hi)
    with jax.named_scope("intra_run"):
        gap = big_l.T[:, :, None] - big_l.T[:, None, :]        # [h,t,j]
        w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0) \
            * jnp.einsum("thn,jhn->htj", c, b, **hi)
        y = y + jnp.einsum("htj,jhp->thp", w, dtx, **hi)
    with jax.named_scope("state_update"):
        add = jnp.einsum("jhn,jhp->jnhp",
                         b * jnp.exp(run_end - big_l)[..., None], dtx, **hi)
        # a run's first row rescales its slot (to zero where fresh: a
        # select, whatever the slot held), then every row adds its own
        # part; every other row rewrites slot 0 with itself
        at = jnp.where(first, slots, 0)
        scale = jnp.where(first[:, None], jnp.exp(run_end), 1.0)
        base = jnp.where(fresh[:, None, None, None], 0.0,
                         states[at] * scale[:, None, :, None])
        states = states.at[at].set(base).at[slots].add(add)
    y = jnp.where(live[:, None, None], y, 0.0)
    return y, pool.at[lead].set(states.reshape(pool[lead].shape))
