"""Shared pieces of the plain references: float32 throughout, no kernels, no
cache, no batching tricks.  On a TPU a float32 matmul runs in lower
precision unless ``jax.default_matmul_precision("highest")`` is set, so
every entry point here sets it.

A reference module exports ``stack(params, tokens, model)`` (tokens [b, s]
-> the final norm's output [b, s, h]), ``head(params, hidden, model)``
(hidden [..., h] -> logits [..., vocab]) and ``logits`` = the head of the
stack.  Training compares whole ``logits``; serving applies the head to the
rows whose tokens were emitted and to no other (lib/check.py).

The references read the *program's* parameter tree (same weights, so the
comparison is of arithmetic, not of initialisation).  Two departures from
the published descriptions follow from that layout and change no result:

* the fused QKV weight is group-major (for each KV head: its query heads,
  then K, then V) instead of three matrices;
* RoPE rotates interleaved pairs (dims 0-1, 2-3, ...), the Meta layout;
  the HF checkpoints' rotate-half layout is the same rotation under a fixed
  permutation of each head's dims, which weight conversion applies.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, theta: float):
    """x [b, s, heads, d]; positions 0..s-1; interleaved pairs."""
    b, s, n, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # [s, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xp = x.reshape(b, s, n, d // 2, 2)
    even, odd = xp[..., 0], xp[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(b, s, n, d)


def split_qkv(qkv, n: int, nkv: int, d: int):
    g = n // nkv
    *lead, _ = qkv.shape
    grouped = qkv.reshape(*lead, nkv, g + 2, d)
    q = grouped[..., :g, :].reshape(*lead, n, d)
    return q, grouped[..., g, :], grouped[..., g + 1, :]


QUERY_BLOCK = 512          # a longer sequence attends this many queries at a time
SCORE_BYTES = 1 << 28      # ... or fewer: a block's float32 scores stay under this


def query_block(s: int, heads: int) -> int:
    """How many queries attend at a time: all ``s`` up to QUERY_BLOCK (the
    plain form), else the largest power of two, at most QUERY_BLOCK and at
    least 8, whose scores ``[heads, block, s]`` float32 fit SCORE_BYTES."""
    if s <= QUERY_BLOCK:
        return s
    fit = max(8, SCORE_BYTES // (4 * heads * s))
    return min(QUERY_BLOCK, 1 << (fit.bit_length() - 1))


def in_query_blocks(attend, q, size: int):
    """``attend(q_block [b, size, ...], start) -> [b, size, w]`` over
    consecutive blocks of ``size`` queries of ``q`` [b, s, ...]; the last
    block is padded and the padding dropped.  Every query row still meets
    its whole key row inside ``attend``, so blocking changes no number."""
    b, s = q.shape[:2]
    blocks = -(-s // size)
    qp = jnp.pad(q, ((0, 0), (0, blocks * size - s)) + ((0, 0),) * (q.ndim - 2))
    out = jax.lax.map(
        lambda i: attend(jax.lax.dynamic_slice_in_dim(qp, i * size, size, axis=1),
                         i * size), jnp.arange(blocks))     # [blocks, b, size, w]
    return out.transpose(1, 0, 2, 3).reshape(b, blocks * size, -1)[:, :s]


def causal_mask(start, size: int, s: int, window: Optional[int]):
    """[size, s]: which of ``s`` keys the queries ``start .. start + size``
    may see (a padded query past the end sees what the last one sees)."""
    qpos = jnp.minimum(start + jnp.arange(size), s - 1)[:, None]
    kpos = jnp.arange(s)[None, :]
    ok = qpos >= kpos
    if window:
        ok &= (qpos - kpos) < window
    return ok


def causal_attention(q, k, v, window: Optional[int]):
    """q [b,s,n,d], k/v [b,s,nkv,d]; softmax in float32 over the keys a
    causal query may see (at most ``window`` of them).  Past QUERY_BLOCK
    positions a block of queries at a time (``query_block``)."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    g = n // nkv

    def attend(qb, start):
        size = qb.shape[1]
        qg = qb.reshape(b, size, nkv, g, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(F32(d))
        ok = causal_mask(start, size, s, window)
        p = jax.nn.softmax(jnp.where(ok[None, None, None], scores, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, size, n * d)

    size = query_block(s, n)
    return attend(q, 0) if size >= s else in_query_blocks(attend, q, size)


def run_layers(block, params: Dict, x, model: Dict):
    """Upcast and apply one layer at a time, so that a deep model's float32
    copy never exists whole."""
    layers = params["layers"]
    depth = jax.tree.leaves(layers)[0].shape[0]
    step = jax.jit(lambda layer, h: block(f32(layer), h, model))
    for i in range(depth):
        x = step(jax.tree.map(lambda a: a[i], layers), x)
    return x


def project(hidden, kernel):
    """hidden [..., h] @ kernel [h, vocab] in float32: an untied head."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda a, w: a @ w.astype(F32))(hidden, kernel)


def emitted_log_probs(logits, tokens):
    """log p(tokens[i]) under row i of ``logits`` [m, vocab] -> [m]."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]


def token_log_probs(logits, tokens):
    """log p(tokens[:, i+1] | tokens[:, :i+1]) -> [b, s-1]."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
