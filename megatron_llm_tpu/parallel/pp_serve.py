"""Pipeline-parallel serving: the engine tick across a ``pp`` mesh axis.

Training has pp>1 (parallel/pipeline.py: 1F1B microbatches over
``collective_permute``) but until ISSUE 20 the serving engine was TP-only,
so a served model had to fit one host's chips.  This module extends the
engine's forward across pipeline stages:

* **Layer placement**: the stacked ``[L, ...]`` layer params and the paged
  K/V pool (``[L, pages, page, row]``, ops/kv_quant.py) are sharded ``P(pp)`` on the
  layer dim — each stage holds ``L/pp`` layers and ONLY its own layers'
  K/V pages (the servable-model-size multiplier: per-stage pool bytes are
  ``1/pp`` of the tp-only pool).  Block tables, the page trie, the
  allocator and the commitment ledger stay host-side and stage-agnostic:
  page ids address the same slot of every stage's pool slice, so nothing
  in generation/ scheduling changes.
* **Microbatching**: a decode/ragged tick of ``R`` rows (``s == 1``)
  splits into ``M = pp`` contiguous row-range microbatches pumped through
  the stages on a ``T = M + pp - 1`` tick scan — decode is the
  steady-state-full pipeline the 1F1B schedule likes (every tick all
  stages run a GEMM, one microbatch apart).  Chunked prefill feeds
  ``[1, chunk]`` (one sequence), which cannot split by rows: it runs
  ``M = 1`` (stages sequential; prefill is not latency-critical and
  stays schedulable against decode ticks).  Contiguous row ranges keep
  intra-tick causality: row ``r1 > r0`` of one request lands in
  microbatch ``m1 >= m0``, and stage ``s`` runs ``m0`` at scan tick
  ``s + m0 < s + m1`` — writes land before the later rows attend.
* **Overlap**: the stage-boundary ``ppermute`` (named scope
  ``stage-permute``) is data-independent of the next tick's own GEMMs
  until the received activation is consumed, so XLA's latency-hiding
  scheduler runs the DMA behind the adjacent stage compute — PR 15's
  ring thesis applied one level up (T3, PAPERS.md).
* **Validity routing**: pipeline fill/drain ticks where ``t - stage`` is
  outside ``[0, M)`` must not touch live pages.  Invalid ticks are
  null-routed through page 0 (the engine's reserved NULL page): per-row
  block tables are zeroed, compressed ``table_index`` rows point at the
  prepended null table and ``horizons`` drop to 0 — garbage compute,
  discarded output, no state mutation.  The same trick the ragged tick
  uses for dead padding rows (ISSUE 11).

Like overlap.py, activation is a trace-time context: the engine's tick
builders wrap their bodies in :func:`activate`, and
``models/language_model.model_forward`` routes the transformer stack
through :func:`pipelined_transformer` when a context is live and the call
carries paged K/V.  ``serve_params`` returns None on pp==1 meshes, so an
inert ``--pp 1`` engine traces byte-for-byte today's program.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.core.parallel_state import PP_AXIS, TP_AXIS
from megatron_llm_tpu.parallel import compat

# Named scope wrapping every stage-boundary ppermute — device profiles
# attribute the hop DMA separately from the stage GEMMs (ISSUE 20
# observability satellite; asserted in HLO by tests and bench --mode pp).
STAGE_PERMUTE_SCOPE = "stage-permute"


class ServeParams:
    """Static pipeline-serving parameters captured at engine build."""

    __slots__ = ("mesh", "pp", "tp")

    def __init__(self, mesh, pp: int, tp: int):
        self.mesh = mesh
        self.pp = pp
        self.tp = tp


def serve_params(cfg, mesh) -> Optional[ServeParams]:
    """Resolve the pipeline-serving context, or None when inert.

    None whenever there is no mesh or the mesh's pp axis is 1 — an engine
    built with ``--pp 1`` (flag set but inert) takes the None path and is
    bitwise today's TP-only program.
    """
    if mesh is None:
        return None
    pp = dict(mesh.shape).get(PP_AXIS, 1)
    if pp <= 1:
        return None
    return ServeParams(mesh, pp, dict(mesh.shape).get(TP_AXIS, 1))


_state = threading.local()


@contextmanager
def activate(ctx: Optional[ServeParams]):
    """Trace-time activation — engine tick builders wrap their bodies."""
    prev = getattr(_state, "ctx", None)
    _state.ctx = ctx
    try:
        yield
    finally:
        _state.ctx = prev


def current() -> Optional[ServeParams]:
    return getattr(_state, "ctx", None)


def _null_route(paged, valid):
    """Point invalid rows at the NULL page so fill/drain ticks are inert.

    ``valid`` is a scalar bool (whole-microbatch validity).  Compressed
    form: index 0 is the prepended null table and horizon 0 means "touch
    no page" (ragged.py's dead-row convention).  Per-row form: page 0 is
    the engine's reserved NULL page, so a zeroed block table writes (and
    reads) only scratch.
    """
    if paged.table_index is not None:
        return paged._replace(
            horizons=jnp.where(valid, paged.horizons, 0),
            table_index=jnp.where(valid, paged.table_index, 0),
        )
    return paged._replace(
        block_tables=jnp.where(valid, paged.block_tables, 0))


def pipelined_transformer(cfg, ctx: ServeParams, stacked_layers, hidden, *,
                          rope, position_ids, kv_caches, paged):
    """Run the layer stack as a pp-stage pipeline over microbatched rows.

    Args mirror the ``transformer_forward`` call in model_forward;
    ``kv_caches`` is the paged pool (``[L, ...]`` leaves, sharded
    ``P(pp)`` on the layer dim by ``PagedKVPool``); a stage hands its own
    ``L/pp`` layers of it to the stack, which carries them through its
    scan and writes them in place (``pool_first_layer``).  Returns
    ``(hidden, new_kv_caches)`` — MoE aux is not plumbed (serving is
    deterministic inference; the engine discards it).
    """
    from megatron_llm_tpu.models.transformer import transformer_forward
    from megatron_llm_tpu.ops.paged_attention import PagedState

    pp = ctx.pp
    b, s = hidden.shape[0], hidden.shape[1]
    # Rows microbatch only in the one-token-per-row regime (decode /
    # ragged / verify ticks): s == 1 and the row count splits evenly.
    # Chunked prefill ([1, chunk]) and odd row counts run M = 1 —
    # sequential stages, correct but bubbled.
    M = pp if (s == 1 and b >= pp and b % pp == 0) else 1
    mbs = b // M
    compressed = paged.table_index is not None

    hidden_mb = hidden.reshape(M, mbs, *hidden.shape[1:])
    pos_mb = position_ids.reshape(M, mbs, *position_ids.shape[1:])
    kv_pos_mb = paged.positions.reshape(M, mbs)
    if compressed:
        # block_tables is the COMPRESSED per-tick table set [T, W] shared
        # by all rows — replicated; per-row index/horizon arrays split.
        meta_mb = (paged.block_tables,
                   paged.horizons.reshape(M, mbs),
                   paged.table_index.reshape(M, mbs))
    else:
        meta_mb = (paged.block_tables.reshape(M, mbs, -1),)

    layer_spec = jax.tree.map(lambda _: P(PP_AXIS), stacked_layers)
    pool_spec = jax.tree.map(lambda _: P(PP_AXIS), kv_caches)
    repl = jax.tree.map(lambda _: P(), (hidden_mb, pos_mb, kv_pos_mb,
                                        meta_mb, rope))

    def body(layers_local, pools_local, x_mb, p_mb, kvp_mb, meta, rp):
        stage = jax.lax.axis_index(PP_AXIS)
        n_local = jax.tree_util.tree_leaves(layers_local)[0].shape[0]
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(carry, t):
            recv, out_buf, pools = carry
            u = t - stage
            valid = (u >= 0) & (u < M)
            mb = jnp.clip(u, 0, M - 1)
            take = lambda a: jax.lax.dynamic_index_in_dim(
                a, mb, 0, keepdims=False)
            inp = jnp.where(stage == 0, take(x_mb), recv)
            if compressed:
                tbl, hor, idx = meta
                pg = PagedState(tbl, take(kvp_mb),
                                horizons=take(hor), table_index=take(idx))
            else:
                pg = PagedState(take(meta[0]), take(kvp_mb))
            pg = _null_route(pg, valid)

            # Fill/drain ticks (u outside [0, M)) skip the stage forward
            # entirely: on a serialized backend the bubble would otherwise
            # burn real GEMM time on discarded output, and on TPU the
            # stage sits idle either way.  The null-routing above stays as
            # defense in depth should the conditional ever be lowered to
            # a select (both branches evaluated): writes still land on
            # the reserved NULL page, never on live state.
            def _run(op):
                inp_, pg_, pools_ = op
                out_, pools_, _ = transformer_forward(
                    cfg, layers_local, inp_,
                    rope=rp, position_ids=take(p_mb),
                    kv_caches=pools_, paged=pg_,
                    layer_offset=stage * n_local,
                    pool_first_layer=stage * n_local,
                )
                return out_, pools_

            def _skip(op):
                inp_, _, pools_ = op
                return jnp.zeros_like(inp_), pools_

            out, pools = jax.lax.cond(valid, _run, _skip,
                                      (inp, pg, pools))
            emit = valid & (stage == pp - 1)
            prev = jax.lax.dynamic_index_in_dim(out_buf, mb, 0,
                                                keepdims=False)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(emit, out, prev), mb, 0)
            with jax.named_scope(STAGE_PERMUTE_SCOPE):
                nxt = jax.lax.ppermute(out, PP_AXIS, perm)
            return (nxt, out_buf, pools), None

        zeros = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
        out_buf = jnp.zeros_like(x_mb)
        (_, out_buf, pools_local), _ = jax.lax.scan(
            tick, (zeros, out_buf, pools_local),
            jnp.arange(M + pp - 1))
        # Only the last stage wrote out_buf (zeros elsewhere): psum over
        # pp broadcasts the emitted activations to every stage.
        return jax.lax.psum(out_buf, PP_AXIS), pools_local

    out_mb, new_caches = compat.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(layer_spec, pool_spec) + repl,
        out_specs=(P(), pool_spec),
        axis_names={PP_AXIS}, check_vma=False,
    )(stacked_layers, kv_caches, hidden_mb, pos_mb, kv_pos_mb,
      meta_mb, rope)
    return out_mb.reshape(hidden.shape), new_caches
