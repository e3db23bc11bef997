"""EQuARX-style quantized data-parallel gradient all-reduce.

Motivation (PAPERS.md, "EQuARX: Efficient Quantized AllReduce in XLA"):
DP gradient sync moves every parameter's gradient across the dp axis each
step — at bf16 that is 2 bytes/param/step of interconnect traffic that
the step cannot hide once the model is large relative to the per-step
compute.  Quantizing the wire format to int8 with per-chunk scales
recovers roughly half of that bandwidth at a bounded numerical cost.

Scheme (:func:`quantized_allreduce_mean`), the classic quantized
reduce-scatter + all-gather decomposition:

1. **chunk + quantize** — the flat gradient pads to ``dp`` equal chunks;
   each rank quantizes every chunk with its own symmetric absmax scale
   (int8 wire format, one fp32 scale per chunk).
2. **reduce-scatter** (``all_to_all``) — chunk ``r`` of every rank lands
   on rank ``r``, still quantized: the wire moves 1 byte/element.
3. **dequant-accumulate** — rank ``r`` dequantizes the ``dp`` versions of
   its chunk with their senders' scales and sums in fp32, then divides by
   ``dp``.  Each contribution is quantized exactly ONCE — no per-hop
   requantization error compounding (the advantage over a quantized ring).
4. **requantize + all-gather** — the mean chunk requantizes under a fresh
   scale and gathers back to every rank (1 byte/element again), then
   dequantizes into the gradient dtype.

Error bound: each element suffers at most one sender-side and one
result-side rounding, ``<= s_in/2 + s_out/2`` with ``s = chunk
absmax/127`` — the figure the loss-delta gate in
tests/test_kv_quant.py measures against a bf16-sync baseline
(docs/guide/quantization.md "Quantized collectives" documents the
accepted delta and when NOT to enable this).

Small leaves (norm scales, biases — ``size < min_quant_size``) keep the
exact ``pmean``: their bytes are negligible and their gradients are the
precision-sensitive ones.

Integration (:func:`make_quantized_dp_grad_fn`): the whole
forward/backward/accumulate runs inside ONE full-manual
``parallel/compat.shard_map`` region over the mesh — each dp rank
computes grads on its local batch shard, then the explicit quantized sync
above replaces the all-reduce XLA would otherwise emit implicitly from
the replicated-params/sharded-batch contraction.  Like the reference's
DDP, the loss is the dp-mean of per-rank masked means (identical to the
global mean whenever shards carry equal loss-mask counts).  Scope:
dp-pure meshes (tp == pp == cp == ep == 1) — the row-parallel tp
all-reduces live inside the forward where XLA owns them; quantizing those
is future work under the same flag family.  ``--quantized_grad_allreduce``
is OFF by default; the bf16-sync path is bitwise untouched.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from megatron_llm_tpu.core.parallel_state import DP_AXIS
from megatron_llm_tpu.parallel import compat

# leaves smaller than this sync exactly (pmean): quantizing a [h] norm
# gradient saves nothing on the wire and costs the most precision
MIN_QUANT_SIZE = 4096

_EPS = 1e-20


def _quant_chunks(x32: jax.Array, n: int):
    """[n, c] fp32 -> (int8 values, [n] fp32 scales), symmetric absmax."""
    s = jnp.max(jnp.abs(x32), axis=1) / 127.0
    q = jnp.clip(jnp.round(x32 / jnp.maximum(s, _EPS)[:, None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q, s


def quantized_allreduce_mean(x: jax.Array, axis_name: str, axis_size: int,
                             min_quant_size: int = MIN_QUANT_SIZE
                             ) -> jax.Array:
    """dp-mean of ``x`` with int8 chunk-quantized traffic (module
    docstring).  Must run inside a manual region binding ``axis_name``;
    returns the mean in ``x``'s dtype, identical bytes on every rank."""
    if axis_size == 1:
        return x
    if x.size < min_quant_size:
        return jax.lax.pmean(x, axis_name)
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % axis_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    c = flat.size // axis_size
    q, s = _quant_chunks(flat.reshape(axis_size, c), axis_size)
    # reduce-scatter: chunk r of every rank -> rank r (quantized wire)
    q_x = jax.lax.all_to_all(q, axis_name, 0, 0)            # [dp, c]
    s_x = jax.lax.all_to_all(s.reshape(axis_size, 1), axis_name, 0, 0)
    acc = jnp.sum(q_x.astype(jnp.float32) * s_x, axis=0) / axis_size
    # requantize the mean chunk, gather quantized, dequantize locally
    s_out = jnp.max(jnp.abs(acc)) / 127.0
    q_out = jnp.clip(jnp.round(acc / jnp.maximum(s_out, _EPS)),
                     -127.0, 127.0).astype(jnp.int8)
    q_g = jax.lax.all_gather(q_out, axis_name, axis=0)      # [dp, c]
    s_g = jax.lax.all_gather(s_out, axis_name, axis=0)      # [dp]
    out = (q_g.astype(jnp.float32) * s_g[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape).astype(x.dtype)


def quantized_dp_supported(cfg, mesh) -> bool:
    """Is the quantized DP sync applicable to this (cfg, mesh)?  dp-pure
    meshes only; anything else keeps the implicit XLA all-reduce."""
    if mesh is None:
        return False
    shape = dict(mesh.shape)
    if shape.get(DP_AXIS, 1) <= 1:
        return False
    others = {k: v for k, v in shape.items() if k != DP_AXIS}
    return all(v == 1 for v in others.values())


def make_quantized_dp_grad_fn(cfg, mesh: Mesh, loss_fn: Callable,
                              num_micro: int, fwd_scope: str = "forward"):
    """Build ``qdp(params, batch, base_key, scale) -> ((loss, metrics),
    grads)`` — the drop-in replacement for the train step's
    grad-accumulation branch when ``--quantized_grad_allreduce`` is on.

    ``loss_fn`` is the family loss (signature of
    models/language_model.loss_from_batch).  The returned callable builds
    the full-manual shard_map at trace time (the batch's pytree structure
    picks the per-leaf input specs), so it composes with jit exactly like
    the branches it replaces."""
    assert quantized_dp_supported(cfg, mesh), (
        "--quantized_grad_allreduce needs a dp-pure mesh (dp > 1, "
        "tp == pp == cp == ep == 1); the tp/pp collectives are emitted "
        "inside the forward where XLA owns them")
    names = set(mesh.axis_names)
    N = int(dict(mesh.shape)[DP_AXIS])
    deterministic = (cfg.model.hidden_dropout == 0.0
                     and cfg.model.attention_dropout == 0.0)

    def body(params, batch, base_key, scale):
        from megatron_llm_tpu.models.language_model import make_rope_cache

        rope = make_rope_cache(cfg)
        rank = jax.lax.axis_index(DP_AXIS)

        def scaled(p, mb, k):
            with jax.named_scope(fwd_scope):
                loss, mets = loss_fn(
                    cfg, p, mb, dropout_key=k,
                    deterministic=deterministic, rope_cache=rope,
                    sp_constraint=None)
            return loss * jax.lax.stop_gradient(scale), mets

        gfn = jax.value_and_grad(scaled, has_aux=True)

        def key_for(idx):
            if deterministic:
                return None
            # per-rank, per-microbatch dropout streams (the baseline's
            # fold_in(base, idx), further folded by dp coordinate so
            # shards never share a pattern)
            return jax.random.fold_in(jax.random.fold_in(base_key, idx),
                                      rank)

        if num_micro == 1:
            (loss, mets), grads = gfn(params, batch, key_for(0))
        else:
            from megatron_llm_tpu.training_step import _split_microbatches

            mbs = _split_microbatches(batch, num_micro)
            first_mb = jax.tree.map(lambda a: a[0], mbs)
            mets0 = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(lambda p, mb: scaled(p, mb, key_for(0))[1],
                               params, first_mb))
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, p.dtype), params)

            def accum(carry, xs):
                g_sum, l_sum, m_sum = carry
                mb, idx = xs
                (l, mets), g = gfn(params, mb, key_for(idx))
                return (jax.tree.map(jnp.add, g_sum, g), l_sum + l,
                        jax.tree.map(jnp.add, m_sum, mets)), None

            (g_sum, l_sum, m_sum), _ = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32), mets0),
                (mbs, jnp.arange(num_micro)))
            inv = 1.0 / num_micro
            grads = jax.tree.map(lambda g: g * inv, g_sum)
            loss = l_sum * inv
            mets = jax.tree.map(lambda m: m * inv, m_sum)

        # THE quantized sync: int8 reduce-scatter + all-gather per leaf
        with jax.named_scope("quantized-dp-allreduce"):
            grads = jax.tree.map(
                lambda g: quantized_allreduce_mean(g, DP_AXIS, N), grads)
        loss = jax.lax.pmean(loss, DP_AXIS)
        mets = jax.tree.map(lambda m: jax.lax.pmean(m, DP_AXIS), mets)
        return (loss, mets), grads

    def qdp(params, batch, base_key, scale):
        bspecs = {k: (P() if k == "token_idx" else P(DP_AXIS))
                  for k in batch}
        mapped = compat.shard_map(
            body, mesh=mesh,
            in_specs=(P(), bspecs, P(), P()),
            out_specs=((P(), P()), P()),
            axis_names=names, check_vma=False)
        return mapped(params, batch, base_key, scale)

    return qdp
