"""Attention core ops: XLA reference path + dispatch to the Pallas flash kernel.

Replaces the reference's ``CoreAttention`` (model/transformer.py:144-278 —
baddbmm scores + fused scale-mask-softmax + bmm context) and its
FlashAttention-2 path (transformer.py:518-600, incl. sliding-window kwargs and
GQA). TPU-native differences:

* GQA is computed *without* broadcast-expanding K/V (the reference expands at
  transformer.py:459-466); we reshape Q to [.., kv_heads, group, ..] and let
  the MXU batch over (kv_heads, group).
* masking is built from static causal/sliding-window structure plus an
  optional per-document segment-id tensor (packed sequences), instead of
  materialized 4D byte masks.
* the hot path on TPU is the Pallas flash kernel (ops/pallas/flash_attention);
  this module provides the numerically-identical XLA fallback and the
  dispatcher.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


@functools.lru_cache(maxsize=None)
def announce_path(op: str, path: str, why: str = "") -> None:
    """Say once, while a program is being traced, which implementation an
    attention op resolved to — so a log shows whether a compiled program
    holds the Pallas kernel or a jnp/XLA substitute, and why."""
    print(f"[attention] {op}: {path}" + (f" ({why})" if why else ""),
          flush=True)


def kernel_region(mesh):
    """(mesh, axis_names) to shard_map a Pallas call over.

    pallas_call is opaque to the GSPMD partitioner, so under a mesh the
    kernel must be mapped explicitly.  Called from inside an enclosing
    shard_map (the pipeline engines manualize pp/cp), the inner shard_map
    must bind the CONTEXT abstract mesh — passing the concrete global mesh
    raises a mesh-mismatch — and manualize every axis not already manual:
    Mosaic kernels reject being left under ANY auto axis, even size-1."""
    from megatron_llm_tpu.parallel import compat

    abstract = compat.get_abstract_mesh()
    if not abstract.empty and abstract.manual_axes:
        return abstract, set(abstract.axis_names) - set(abstract.manual_axes)
    return mesh, set(mesh.axis_names)


def _flash_sharded(q, k, v, segment_ids, scale, sliding_window, block_q,
                   block_kv, causal=True):
    """Run the Pallas kernel, wrapped in shard_map when a non-trivial mesh is
    active: batch over dp, heads over tp (attention is embarrassingly
    parallel over both — the same decomposition the reference gets from
    per-rank processes). Sequence stays whole here; context parallelism
    (ring attention) shards it separately in parallel/ring.
    """
    from megatron_llm_tpu.core import parallel_state as ps
    from megatron_llm_tpu.ops.pallas.flash_attention import flash_attention

    kwargs = dict(causal=causal, sliding_window=sliding_window, scale=scale,
                  block_q=block_q, block_kv=block_kv)
    if not ps.mesh_is_initialized():
        return flash_attention(q, k, v, segment_ids=segment_ids, **kwargs)
    mesh = ps.get_global_mesh()
    if (mesh.shape.get(ps.DP_AXIS, 1) == 1 and mesh.shape.get(ps.TP_AXIS, 1) == 1
            and mesh.shape.get(ps.EP_AXIS, 1) == 1):
        return flash_attention(q, k, v, segment_ids=segment_ids, **kwargs)

    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu.parallel.compat import shard_map

    mesh, names = kernel_region(mesh)
    qs = P(ps.DATA_AXES, None, ps.TP_AXIS, None)
    kvs = P(ps.DATA_AXES, None, ps.TP_AXIS, None)
    segs = P(ps.DATA_AXES, None)
    if segment_ids is None:
        fn = shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, **kwargs),
            mesh=mesh, in_specs=(qs, kvs, kvs), out_specs=qs,
            axis_names=names, check_vma=False,
        )
        return fn(q, k, v)
    fn = shard_map(
        lambda q_, k_, v_, s_: flash_attention(q_, k_, v_, segment_ids=s_, **kwargs),
        mesh=mesh, in_specs=(qs, kvs, kvs, segs), out_specs=qs,
        axis_names=names, check_vma=False,
    )
    return fn(q, k, v, segment_ids)


def make_attention_bias(
    seq_len: int,
    kv_len: Optional[int] = None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    segment_ids_q: Optional[jax.Array] = None,
    segment_ids_kv: Optional[jax.Array] = None,
    token_idx: Optional[jax.Array] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Build an additive attention bias [*, 1, q_len, kv_len].

    ``segment_ids`` [batch, seq] gate cross-document attention for packed
    sequences (reference --reset_attention_mask / attention_mask_in_length
    varlen path, instruction_dataset.py + transformer.py:540-582).
    """
    kv_len = kv_len if kv_len is not None else seq_len
    if token_idx is not None:
        # zigzag/permuted layouts: causal structure follows the original
        # token order, not the storage order (parallel/ring.py)
        q_pos = token_idx[:, None]
        kv_pos = token_idx[None, :]
    else:
        q_pos = jnp.arange(seq_len)[:, None]
        kv_pos = jnp.arange(kv_len)[None, :]
    allowed = jnp.ones((seq_len, kv_len), dtype=bool)
    if causal:
        allowed &= q_pos >= kv_pos
    if sliding_window is not None:
        # Mistral sliding window: attend to at most the last W positions
        # (transformer.py:529-537).
        allowed &= q_pos - kv_pos < sliding_window
    bias = jnp.where(allowed, 0.0, NEG_INF).astype(dtype)[None, None]
    if segment_ids_q is not None:
        same = segment_ids_q[:, :, None] == segment_ids_kv[:, None, :]
        bias = bias + jnp.where(same, 0.0, NEG_INF).astype(dtype)[:, None]
    return bias


def xla_attention(
    q: jax.Array,  # [b, sq, n_heads, d]
    k: jax.Array,  # [b, skv, n_kv_heads, d]
    v: jax.Array,  # [b, skv, n_kv_heads, d]
    bias: Optional[jax.Array] = None,  # [b or 1, 1, sq, skv]
    scale: Optional[float] = None,
    softmax_fp32: bool = True,
    dropout_rate: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
) -> jax.Array:
    """Grouped-query attention via einsum; exact softmax. Returns [b, sq, n, d]."""
    b, sq, n, d = q.shape
    _, skv, nkv, _ = k.shape
    assert n % nkv == 0
    g = n // nkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, sq, nkv, g, d)
    # scores [b, nkv, g, sq, skv]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg * scale, k)
    if softmax_fp32:
        scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias[:, :, None]  # broadcast over group dim
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, n, v.shape[-1])   # v may be narrower (MLA)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
    token_idx: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    use_flash: bool = True,
    dropout_rate: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    zigzag: bool = False,
) -> jax.Array:
    """Dispatch between ring attention (cp > 1), the Pallas flash kernel,
    and the XLA fallback. ``zigzag`` declares the standard apply_zigzag
    token layout (cfg --cp_zigzag), which lets the ring path use the
    striped flash kernels instead of the jnp fallback."""
    sq = q.shape[1]

    from megatron_llm_tpu.core import parallel_state as ps

    cp = (
        ps.get_context_parallel_world_size()
        if ps.mesh_is_initialized()
        else 1
    )
    if cp > 1:
        assert q.shape[-1] == v.shape[-1], (
            "ring attention needs equal qk and v widths (latent attention "
            "has no context-parallel form)")
        assert bias is None and dropout_rate == 0.0, (
            "context parallelism supports structural masking only "
            "(causal/sliding-window/segment), no bias or attention dropout"
        )
        from megatron_llm_tpu.parallel.ring import ring_attention

        announce_path("dense", "ring", f"cp={cp}")
        return ring_attention(
            q, k, v, segment_ids=segment_ids, token_idx=token_idx,
            causal=causal, sliding_window=sliding_window, scale=scale,
            zigzag=zigzag,
        )
    # compile-TARGET platform, not the host backend: AOT lowering for a TPU
    # topology on a CPU host must still pick the flash kernel.  Bidirectional
    # (BERT / T5 encoder) runs the kernel with causal masking off.
    target = ps.target_platform()
    refusal = (
        "use_flash_attn is off" if not use_flash
        else "explicit bias" if bias is not None
        else "attention dropout" if dropout_rate != 0.0
        # the kernel masks by storage order only
        else "permuted token order" if token_idx is not None
        else f"target platform is {target}" if target != "tpu"
        else f"seq {sq} < 128" if sq < 128
        else f"head_dim {q.shape[-1]}" if q.shape[-1] not in (64, 128, 256)
        # the kernel's blocks and its backward assume one head width
        else (f"qk width {q.shape[-1]} differs from v width {v.shape[-1]}")
        if q.shape[-1] != v.shape[-1]
        else None
    )
    if refusal is None:
        announce_path("dense", "pallas")
        return _flash_sharded(
            q, k, v, segment_ids, scale, sliding_window, block_q, block_kv,
            causal=causal,
        )
    announce_path("dense", "xla", refusal)
    if bias is None:
        seg_q = seg_kv = segment_ids
        bias = make_attention_bias(
            sq, k.shape[1], causal=causal, sliding_window=sliding_window,
            segment_ids_q=seg_q, segment_ids_kv=seg_kv, token_idx=token_idx,
        )
    return xla_attention(
        q, k, v, bias=bias, scale=scale,
        dropout_rate=dropout_rate, dropout_key=dropout_key,
    )
