"""Power retention (degree 2): gated linear attention whose kernel is the
square of the query-key product, so a sequence's whole past is a state of
constant size and no key or value is kept (Brumby-14B; arXiv:2507.04239).

For one KV head, with ``l_t = log sigmoid(gate_t) <= 0`` and ``L_t`` its
running sum, three forms of the same numbers:

* **attention** (:func:`retention_attention`): ``a_tj = exp(L_t - L_j)
  (q_t . k_j)^2`` for ``j <= t``, ``y_t = sum_j a_tj v_j / (sum_j a_tj +
  eps)``: quadratic, what the plain reference computes;
* **recurrent** (:func:`retention_step`): ``S_t = exp(l_t) S_{t-1} +
  phi(k_t) v_t^T``, ``z_t = exp(l_t) z_{t-1} + phi(k_t)``, ``y_t =
  phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` with ``phi(a) . phi(b) =
  (a . b)^2``;
* **chunked** (:func:`retention_chunk`): a run of rows reads the state
  once, each row takes the state's part plus the attention form over the
  run's own earlier rows, and the state is written once.

``phi`` is laid out in TILES (:func:`phi`): a head of ``d`` values is
``d / 8`` blocks of 8, and the feature vector holds, for every pair of
blocks ``I <= J``, the 8 x 8 products ``a_i a_j`` (times ``sqrt 2`` where
``I < J``): ``d/8 (d/8 + 1) / 2`` tiles of 64, 8,704 values at ``d = 128``
(the minimal symmetric square is 8,256, the full outer product 16,384).
``phi(a) . phi(b) = (a . b)^2`` exactly: a tile ``I < J`` stands for the
ordered pairs of (I, J) and of (J, I), a tile ``I = I`` for its own.

The state is float32 whatever the activations are: a sum that is decayed
and added to at every token for thousands of tokens.  It is STORED
transposed, ``S^T [v, D]`` (values on sublanes, features on lanes), beside
``z [1, D]``: the layout the tick's kernel reads and writes in place
(ops/pallas/retention.py).  :func:`retention_tick` is the tick's ``jnp``
form, the fallback off the TPU and what the tests hold the kernel to.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8            # values of a head in one block of the feature tiles
EPS = 1e-6           # added to the normaliser (the sum of a row's weights)
F32 = jnp.float32


def feature_dim(d: int) -> int:
    """Values of ``phi`` for a head of ``d``: every pair of blocks ``I <=
    J`` as one 8 x 8 tile."""
    assert d % BLOCK == 0, f"head_dim {d} is not whole blocks of {BLOCK}"
    nb = d // BLOCK
    return nb * (nb + 1) // 2 * BLOCK * BLOCK


def _selectors(d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(left [D], right [D], coef [D])``: feature ``f`` is ``coef[f] *
    a[left[f]] * a[right[f]]``."""
    nb = d // BLOCK
    bi, bj = np.triu_indices(nb)                      # tiles, I <= J
    i = bi[:, None, None] * BLOCK + np.arange(BLOCK)[None, :, None]
    j = bj[:, None, None] * BLOCK + np.arange(BLOCK)[None, None, :]
    i, j = np.broadcast_arrays(i, j)
    coef = np.where(bi == bj, 1.0, np.sqrt(2.0))[:, None, None]
    coef = np.broadcast_to(coef, i.shape)
    return (i.reshape(-1), j.reshape(-1),
            coef.reshape(-1).astype(np.float32))


def phi(a: jax.Array) -> jax.Array:
    """``a [..., d]`` -> ``[..., feature_dim(d)]`` float32.  Two one-hot
    matmuls (exact at ``highest``: a selection) and a product: one GEMM
    over all of a tick's rows, which the MXU takes whole."""
    d = a.shape[-1]
    left, right, coef = _selectors(d)
    eye = np.eye(d, dtype=np.float32)
    a = a.astype(F32)
    with jax.default_matmul_precision("highest"):
        return (a @ eye[:, left]) * (a @ eye[:, right]) * coef


def _group(q: jax.Array, nkv: int) -> jax.Array:
    """[..., n, d] -> [..., nkv, g, d]: a KV head's query heads."""
    *lead, n, d = q.shape
    return q.reshape(*lead, nkv, n // nkv, d)


# ---------------------------------------------------------------------------
# The three forms over dense [b, s] sequences
# ---------------------------------------------------------------------------


def retention_attention(q, k, v, log_decay):
    """The attention form.  q [b,s,n,d]; k, v [b,s,nkv,d]; log_decay
    [b,s,nkv] (<= 0).  Returns [b,s,n,d] float32."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    q, k, v = (t.astype(F32) for t in (q, k, v))
    big_l = jnp.cumsum(log_decay.astype(F32), axis=1)          # [b,s,nkv]
    qk = jnp.einsum("btkgd,bjkd->bkgtj", _group(q, nkv), k,
                    precision="highest")
    gap = big_l.transpose(0, 2, 1)[:, :, None, :, None] \
        - big_l.transpose(0, 2, 1)[:, :, None, None, :]
    seen = jnp.tril(jnp.ones((s, s), bool))
    w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)) * qk * qk, 0.0)
    num = jnp.einsum("bkgtj,bjkd->btkgd", w, v, precision="highest")
    den = w.sum(-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + EPS)).reshape(b, s, n, d)


class State(NamedTuple):
    """``s`` ``[..., nkv, d, D]`` (the state transposed: values x
    features) and ``z`` ``[..., nkv, 1, D]``, float32."""

    s: jax.Array
    z: jax.Array


def zero_state(lead: Tuple[int, ...], nkv: int, d: int) -> State:
    big_d = feature_dim(d)
    return State(jnp.zeros((*lead, nkv, d, big_d), F32),
                 jnp.zeros((*lead, nkv, 1, big_d), F32))


def retention_step(state: State, q, k, v, log_decay):
    """One token of the recurrent form.  q [b,n,d]; k, v [b,nkv,d];
    log_decay [b,nkv]; state leaves lead with [b].  Returns (y [b,n,d],
    the new state)."""
    b, n, d = q.shape
    nkv = k.shape[1]
    dec = jnp.exp(log_decay.astype(F32))[..., None, None]
    pk = phi(k)                                               # [b,nkv,D]
    s = dec * state.s + v.astype(F32)[..., :, None] * pk[..., None, :]
    z = dec * state.z + pk[..., None, :]
    pq = phi(_group(q, nkv))                                  # [b,nkv,g,D]
    num = jnp.einsum("bkgf,bkdf->bkgd", pq, s, precision="highest")
    den = jnp.einsum("bkgf,bkf->bkg", pq, z[..., 0, :], precision="highest")
    return (num / (den[..., None] + EPS)).reshape(b, n, d), State(s, z)


def retention_recurrent(q, k, v, log_decay):
    """The recurrent form over [b, s]: a scan of :func:`retention_step`
    from a zero state."""
    b, s, n, d = q.shape
    state = zero_state((b,), k.shape[2], d)

    def one(state, xs):
        y, state = retention_step(state, *xs)
        return state, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, log_decay))
    _, ys = jax.lax.scan(one, state, xs)
    return jnp.moveaxis(ys, 0, 1)


def retention_chunk(state: State, q, k, v, log_decay):
    """One run of rows against the state before it.  q [b,r,n,d]; k, v
    [b,r,nkv,d]; log_decay [b,r,nkv].  Returns (y [b,r,n,d], the state
    after the run)."""
    b, r, n, d = q.shape
    nkv = k.shape[2]
    q, k, v = (t.astype(F32) for t in (q, k, v))
    big_l = jnp.cumsum(log_decay.astype(F32), axis=1).transpose(0, 2, 1)
    qg = _group(q, nkv)                                       # [b,r,nkv,g,d]
    with jax.named_scope("state_query"):
        pq = phi(qg) * jnp.exp(big_l).transpose(0, 2, 1)[..., None, None]
        num = jnp.einsum("brkgf,bkdf->brkgd", pq, state.s,
                         precision="highest")
        den = jnp.einsum("brkgf,bkf->brkg", pq, state.z[..., 0, :],
                         precision="highest")
    with jax.named_scope("intra_run"):
        qk = jnp.einsum("btkgd,bjkd->bkgtj", qg, k, precision="highest")
        gap = big_l[:, :, None, :, None] - big_l[:, :, None, None, :]
        seen = jnp.tril(jnp.ones((r, r), bool))
        w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)) * qk * qk,
                      0.0)
        num = num + jnp.einsum("bkgtj,bjkd->btkgd", w, v,
                               precision="highest")
        den = den + w.sum(-1).transpose(0, 3, 1, 2)
    with jax.named_scope("state_update"):
        to_end = jnp.exp(big_l[..., -1:] - big_l)             # [b,nkv,r]
        pk = phi(k) * to_end.transpose(0, 2, 1)[..., None]    # [b,r,nkv,D]
        whole = jnp.exp(big_l[..., -1])[..., None, None]
        s = whole * state.s + jnp.einsum(
            "brkd,brkf->bkdf", v, pk, precision="highest")
        z = whole * state.z + pk.sum(1)[..., None, :]
    return (num / (den[..., None] + EPS)).reshape(b, r, n, d), State(s, z)


def retention_chunked(q, k, v, log_decay, chunk: int = 64):
    """The chunked form over [b, s]: a scan of :func:`retention_chunk` over
    runs of ``chunk`` rows from a zero state (the dense forward and, by
    autodiff, the trainer).  A padded tail carries zero keys and no decay,
    so it leaves the state as it was; its outputs are dropped."""
    b, s, n, d = q.shape
    chunk = min(chunk, s)
    runs = -(-s // chunk)
    pad = runs * chunk - s

    def cut(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(
            t.reshape(b, runs, chunk, *t.shape[2:]), 1, 0)

    def one(state, xs):
        y, state = retention_chunk(state, *xs)
        return state, y

    _, ys = jax.lax.scan(one, zero_state((b,), k.shape[2], d),
                         tuple(cut(t) for t in (q, k, v, log_decay)))
    return jnp.moveaxis(ys, 0, 1).reshape(b, runs * chunk, n, d)[:, :s]


# ---------------------------------------------------------------------------
# The tick: ragged rows against a pool of per-slot states
# ---------------------------------------------------------------------------


def tick_runs(slots: jax.Array, positions: jax.Array):
    """``(live, first, fresh)`` [R] bool from the data a tick carries: a
    row is LIVE where its slot is not the null slot 0; it goes on with the
    row before it where that row has its slot and the position before its
    own, else it is the FIRST of a run; a run that starts at position 0
    starts a sequence, whose state is zero whatever the slot held (FRESH:
    the slot's reset, inside the tick's own program)."""
    live = slots > 0
    prev_slot = jnp.concatenate([jnp.zeros((1,), slots.dtype), slots[:-1]])
    prev_pos = jnp.concatenate([jnp.full((1,), -2, positions.dtype),
                                positions[:-1]])
    goes_on = live & (prev_slot == slots) & (prev_pos + 1 == positions)
    first = live & ~goes_on
    return live, first, first & (positions == 0)


def retention_tick(q, k, v, log_decay, state: State, slots, positions,
                   layer=None):
    """The tick's rows in the chunked form, ``jnp`` throughout.  q [R,n,d];
    k, v [R,nkv,d]; log_decay [R,nkv]; ``state`` the pool ``[(layers,)
    slots+1, nkv, ...]`` (``layer``: which of its layers); ``slots`` [R]
    each row's state slot (0: a dead row, which touches no state),
    ``positions`` [R].  Rows of one run (:func:`tick_runs`) are
    consecutive.  Returns (y [R,n,d] float32, the pool)."""
    r, n, d = q.shape
    nkv = k.shape[1]
    q, k, v = (t.astype(F32) for t in (q, k, v))
    live, first, fresh = tick_runs(slots, positions)
    run = jnp.cumsum(first) * live                  # 0: dead; runs from 1
    same = (run[:, None] == run[None, :]) & live[:, None]
    seen = same & (jnp.arange(r)[:, None] >= jnp.arange(r)[None, :])
    ld = jnp.where(live[:, None], log_decay.astype(F32), 0.0)
    # L_t within the run: the decays of the run's rows up to and with t
    # (float32 sums: a TPU's default matmul would round them to bfloat16)
    big_l = jnp.einsum("tj,jk->tk", seen.astype(F32), ld,
                       precision="highest")                    # [R,nkv]
    run_end = jnp.einsum("tj,jk->tk", same.astype(F32), ld,
                         precision="highest")
    lead = () if layer is None else (layer,)      # the pool's layer, if any
    pool_s, pool_z = state.s[lead], state.z[lead]
    # the run's state before it: zero for a fresh run
    fresh_run = (same & fresh[None, :]).any(axis=1)
    keep = (~fresh_run & live)[:, None, None, None]
    qg = _group(q, nkv)
    with jax.named_scope("state_query"):
        s0 = jnp.where(keep, pool_s[slots], 0.0)               # [R,nkv,d,D]
        z0 = jnp.where(keep, pool_z[slots], 0.0)
        pq = phi(qg) * jnp.exp(big_l)[..., None, None]
        num = jnp.einsum("rkgf,rkdf->rkgd", pq, s0, precision="highest")
        den = jnp.einsum("rkgf,rkf->rkg", pq, z0[..., 0, :],
                         precision="highest")
    with jax.named_scope("intra_run"):
        qk = jnp.einsum("tkgd,jkd->kgtj", qg, k, precision="highest")
        gap = big_l.T[:, None, :, None] - big_l.T[:, None, None, :]
        w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)) * qk * qk,
                      0.0)
        num = num + jnp.einsum("kgtj,jkd->tkgd", w, v, precision="highest")
        den = den + w.sum(-1).transpose(2, 0, 1)
    with jax.named_scope("state_update"):
        pk = phi(k) * (jnp.exp(run_end - big_l)
                       * live[:, None])[..., None]             # [R,nkv,D]
        # a run's first row rescales its slot (to zero where fresh: a
        # select, whatever the slot held), then every row adds its own
        # part; every other row rewrites slot 0 with itself
        scale = jnp.where(first[:, None], jnp.exp(run_end), 1.0)
        zero = fresh[:, None, None, None]
        at = jnp.where(first, slots, 0)

        def sweep(whole, add):
            base = jnp.where(zero, 0.0,
                             whole[lead][at] * scale[..., None, None])
            return whole.at[(*lead, at)].set(base).at[
                (*lead, slots)].add(add)

        new_s = sweep(state.s, v[..., :, None] * pk[..., None, :])
        new_z = sweep(state.z, pk[..., None, :])
    y = num / (den[..., None] + EPS)
    y = jnp.where(live[:, None, None, None], y, 0.0)
    return y.reshape(r, n, d), State(new_s, new_z)
