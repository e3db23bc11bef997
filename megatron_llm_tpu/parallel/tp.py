"""Tensor-parallel + sequence-parallel sharding rules.

This module is the TPU-native replacement of the reference's explicit TP layer
classes (megatron/core/tensor_parallel/layers.py: ColumnParallelLinear:410,
RowParallelLinear:566, VocabParallelEmbedding:128) and its conjugate-pair
autograd collectives (mappings.py:13-278). Instead of classes issuing NCCL
calls, parallelism is *data placement*: every parameter gets a
``PartitionSpec`` over the (dp, pp, cp, tp) mesh and XLA inserts exactly the
collectives the reference hand-codes —

* column-parallel linear  = kernel sharded on its output axis (`tp`);
  forward needs no comm (identity copy, mappings.py:253-254)
* row-parallel linear     = kernel sharded on its input axis; the contraction
  produces the all-reduce (mappings.py:257) or, with sequence parallelism,
  a reduce-scatter onto the seq-sharded result (layers.py:292)
* vocab-parallel embedding/head = table sharded on the vocab axis; the lookup
  masked-gather + all-reduce (layers.py:187-210) is XLA's gather lowering
* sequence parallelism    = activation sharding constraint putting the seq
  axis on `tp` between blocks (scatter/gather regions, mappings.py:191-247)

Shardings are derived from parameter-path rules, not stored per-layer, so the
same tree works for any tp/pp/dp and for checkpoint resharding.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu.core.parallel_state import (
    CP_AXIS,
    DATA_AXES,
    DP_AXIS,
    EP_AXIS,
    PP_AXIS,
    TP_AXIS,
)

# Grad accumulation / FSDP-style extra sharding could compose here later.


def _spec_for_path(path: tuple, ndim: int, stacked: bool) -> P:
    """Sharding rule for one parameter, keyed on its tree path.

    ``stacked`` marks per-layer parameters carrying a leading layer axis
    (from init_stacked_layers / scan).
    """
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    # stacked per-layer params carry the layer axis first; sharding it over
    # ``pp`` IS pipeline stage placement (pp=1 meshes make it a no-op)
    lead = (PP_AXIS,) if stacked else ()

    def spec(*rest):
        return P(*lead, *rest)

    if "word_embeddings" in names:
        return P(TP_AXIS, None)  # vocab-parallel (VocabParallelEmbedding)
    if "position_embeddings" in names or "tokentype_embeddings" in names:
        return P(None, None)
    if "lm_head_bias" in names or "vocab_bias" in names:
        return P(TP_AXIS)  # vocab-parallel logits bias (BERT/T5 heads)
    if "mlm_head" in names or "pooler" in names or "binary_head" in names:
        # BERT transform/pooler/binary heads: small [h, h]-ish, replicated
        return P(*lead, *([None] * (ndim - len(lead))))
    if "lm_head" in names:
        return P(None, TP_AXIS)  # column-parallel output head
    if "qkv" in names:
        if names[-1] in ("kernel", "kernel_q"):
            return spec(None, TP_AXIS)  # column-parallel: shard fused head dim
        return spec(TP_AXIS)  # bias
    if "cross_attention" in names and names[-2] in ("q", "kv"):
        # T5 decoder inter-attention projections: column-parallel over heads
        if names[-1] in ("kernel", "kernel_q"):
            return spec(None, TP_AXIS)
        return spec(TP_AXIS)
    if "dense" in names:
        if names[-1] in ("kernel", "kernel_q"):
            return spec(TP_AXIS, None)  # row-parallel: shard input (head) dim
        return spec(None)  # row-parallel bias is replicated (added post-reduce)
    if "router" in names:
        # MoE router [h, E]: small, fp32, replicated (models/moe.py)
        return spec(*([None] * (ndim - len(lead))))
    if "experts" in names:
        # MoE expert FFN stacks: leading expert axis sharded over ep, the
        # ffn axis over tp — each (ep, tp) shard holds E/ep experts' tp-slice
        # (column/row-parallel per expert, exactly the dense fc1/fc2 rule).
        if "fc1" in names:
            if names[-1] in ("kernel", "kernel_q"):
                # [E, 2, h, ffn] (GLU) or [E, h, ffn]
                return (spec(EP_AXIS, None, None, TP_AXIS)
                        if ndim == 4 + len(lead) else spec(EP_AXIS, None, TP_AXIS))
            # bias [E, 2, ffn] or [E, ffn]
            return (spec(EP_AXIS, None, TP_AXIS)
                    if ndim == 3 + len(lead) else spec(EP_AXIS, TP_AXIS))
        if "fc2" in names:
            if names[-1] in ("kernel", "kernel_q"):
                return spec(EP_AXIS, TP_AXIS, None)  # [E, ffn, h] row-parallel
            return spec(EP_AXIS, None)  # [E, h] added post-reduce
    if "fc1" in names:
        if names[-1] in ("kernel", "kernel_q"):
            # [h, 2, ffn] (GLU) or [h, ffn]: shard the ffn axis
            return spec(None, None, TP_AXIS) if ndim == 3 + len(lead) else spec(None, TP_AXIS)
        return spec(None, TP_AXIS) if ndim == 2 + len(lead) else spec(TP_AXIS)
    if "fc2" in names:
        if names[-1] in ("kernel", "kernel_q"):
            return spec(TP_AXIS, None)  # row-parallel
        return spec(None)
    # norms, everything else: replicated (layer-stacked keeps lead axis)
    return P(*lead, *([None] * (ndim - len(lead))))


def param_partition_specs(params: Any) -> Any:
    """Build a PartitionSpec pytree mirroring ``params``.

    Works on a params tree or a tree of ShapeDtypeStruct (for eval_shape-based
    initialization without materializing).
    """

    def rule(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        stacked = "layers" in names or "decoder_layers" in names
        return _spec_for_path(path, leaf.ndim, stacked)

    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(mesh: Mesh, params: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_partition_specs(params)
    )


# ---------------------------------------------------------------------------
# Activation sharding
# ---------------------------------------------------------------------------


def batch_spec(sequence_parallel: bool, context_parallel: bool = False) -> P:
    """Spec for [batch, seq, ...] activations on the residual stream.

    Sequence parallelism (reference §2.1 SP row: scatter along seq between TP
    ranks in LN/dropout regions) = putting the seq axis on `tp` here; XLA then
    emits the all-gather before column-linears and the reduce-scatter after
    row-linears exactly as layers.py:225-296 does by hand.

    Context parallelism stacks on top: the seq axis is sharded over cp always
    (ring attention, parallel/ring.py) and additionally over tp in the
    LN/dropout regions when SP is also on.
    """
    if context_parallel:
        seq = (CP_AXIS, TP_AXIS) if sequence_parallel else CP_AXIS
    else:
        seq = TP_AXIS if sequence_parallel else None
    return P(DATA_AXES, seq, None)


def data_spec(context_parallel: bool = False) -> P:
    """Spec for integer batch tensors [batch, seq]: batch over (dp, ep), and
    the seq axis over cp when context parallelism is active."""
    return P(DATA_AXES, CP_AXIS if context_parallel else None)


def batch_shardings(cfg, mesh: Mesh, batch: Any) -> Any:
    """Per-key shardings for a batch dict: [b, s] tensors get the data spec,
    rank-1 per-sample tensors (e.g. BERT ``is_random``) shard over dp only,
    and ``token_idx`` (the [s] zigzag index vector) shards over cp."""
    import numpy as np

    cp = cfg.parallel.context_parallel_size > 1
    d = NamedSharding(mesh, data_spec(cp))
    per_sample = NamedSharding(mesh, P(DATA_AXES))
    idx = NamedSharding(mesh, P(CP_AXIS) if cp else P(None))

    def spec_for(k, v):
        if k == "token_idx":
            return idx
        ndim = getattr(v, "ndim", None)
        if ndim is None:
            ndim = np.asarray(v).ndim
        return per_sample if ndim == 1 else d

    return {k: spec_for(k, v) for k, v in batch.items()}


def make_sp_constraint(cfg, mesh: Optional[Mesh] = None):
    """Return a callable constraining residual-stream activations, or None."""
    spec = batch_spec(
        cfg.parallel.sequence_parallel,
        cfg.parallel.context_parallel_size > 1,
    )

    def constrain(x):
        return jax.lax.with_sharding_constraint(x, spec)

    return constrain


def logits_spec() -> P:
    """Logits [b, s, vocab]: vocab sharded over tp (vocab-parallel CE)."""
    return P(DATA_AXES, None, TP_AXIS)


# ---------------------------------------------------------------------------
# Overlap-aware apply functions (parallel/overlap.py)
# ---------------------------------------------------------------------------
#
# The spec rules above tell XLA *where* tensors live; these apply functions
# are the explicit interception point for *how* the TP collectives run.
# The transformer sublayers route their row/column projections through
# them: inactive (the default --tp_overlap off, tp == 1, pp/cp layouts,
# quantized/fp8 kernels) they ARE the plain projection, byte for byte;
# active, the projection becomes the chunked collective-matmul ring that
# pipelines the all-reduce/reduce-scatter (row) or all-gather (column+SP)
# against its own GEMM.  Lazy import keeps tp.py free of a hard overlap
# dependency for spec-only users (checkpoint resharding tools).


def apply_row_parallel(cfg, p, x, linear):
    """Row-parallel projection (attention ``dense``, ``fc2``)."""
    from megatron_llm_tpu.parallel import overlap

    return overlap.row_parallel(cfg, p, x, linear)


def apply_column_parallel(cfg, p, x, linear):
    """Column-parallel projection (``qkv``, ``fc1``)."""
    from megatron_llm_tpu.parallel import overlap

    return overlap.column_parallel(cfg, p, x, linear)
