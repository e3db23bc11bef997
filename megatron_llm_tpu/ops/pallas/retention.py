"""The tick's state sweep for power retention (ops/retention.py): ONE
kernel reads each run's state once, walks the run's rows on it, and writes
it once, in place.

Grid ``(kv head, row)``, the rows innermost and in order.  A row's blocks
of the state pool are its SLOT's (a scalar-prefetched index), so while
consecutive rows name one slot (a run: rows of one sequence at consecutive
positions) the state's block stays where it is in VMEM and nothing moves
between HBM and it; the block is fetched when the slot changes and written
back when the next one is.  A decode row is a run of one.  A dead row
names the slot of the live row before it (``blk``), so it moves nothing
either, and its step computes nothing: a dead row touches no state.

Inside, the run is walked ROW BY ROW in the recurrent form on the
VMEM-resident state, not by the chunked form's matmuls: ``S <- exp(l) S +
v phi(k)^T`` (the state is stored transposed, ``[v, D]``: a lane-broadcast
column times a sublane-broadcast row), then ``y = phi(q) S^T`` for the KV
head's query heads as one ``[8, D] x [v, D]^T`` matmul.  HBM sees what the
chunked form would show it (one read and one write of the state a run);
what a prompt run pays is VMEM passes.  A run that starts a sequence
(``fresh``: position 0) takes a zero state whatever the slot held.

The feature rows come from the caller (``ops/retention.phi`` over all of
the tick's rows at once: two GEMMs the MXU takes whole); a program's block
of them is ``[8, D]``: the KV head's query heads first, its key at row
``KEY_ROW``, zeros behind.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops import retention as ret

ROWS = 8          # sublanes of a program's feature block
KEY_ROW = ROWS - 1


def _sweep_kernel(layer_ref, blk_ref, flag_ref,      # scalar prefetch
                  feat_ref, vt_ref, dec_ref, s_in, z_in,
                  y_ref, s_out, z_out, *, eps):
    del layer_ref, blk_ref
    row = pl.program_id(1)
    flags = flag_ref[row]
    live = (flags & 1) != 0
    first = (flags & 2) != 0
    fresh = (flags & 4) != 0

    def step(s_prev, z_prev):
        feat = feat_ref[...]                                   # [8, D]
        pk = feat[KEY_ROW:KEY_ROW + 1, :]                      # [1, D]
        dec = dec_ref[...]                                     # [1, 1]
        s = s_prev * dec + vt_ref[...] * pk                    # [v, D]
        z = z_prev * dec + pk
        s_out[...] = s
        z_out[...] = z
        num = jax.lax.dot_general(
            feat, s, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                # [8, v]
        den = jnp.sum(feat * z, axis=1, keepdims=True)         # [8, 1]
        y_ref[...] = num / (den + eps)

    @pl.when(live & fresh)
    def _():
        step(jnp.zeros(s_out.shape, jnp.float32),
             jnp.zeros(z_out.shape, jnp.float32))

    @pl.when(live & first & jnp.logical_not(fresh))
    def _():
        step(s_in[...], z_in[...])

    @pl.when(live & jnp.logical_not(first))
    def _():
        step(s_out[...], z_out[...])

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


def block_slots(slots: jax.Array, live: jax.Array) -> jax.Array:
    """The slot whose blocks a row's step holds: its own where it is live,
    else that of the last live row before it (of the first live row, or
    slot 0, where none is), so that a dead row changes no block."""
    r = slots.shape[0]
    at = jnp.where(live, jnp.arange(r), -1)
    last = jax.lax.cummax(at)
    first_live = jnp.argmax(live)
    return slots[jnp.where(last >= 0, last, first_live)]


def retention_sweep(q, k, v, log_decay, state: ret.State, slots, positions,
                    layer, *, interpret: bool = False):
    """The tick's rows against the layered pool ``state`` (``s`` ``[L,
    slots+1, nkv, d, D]``, ``z`` ``[L, slots+1, nkv, 1, D]``), layer
    ``layer`` of it, in place.  Arguments as ``ops/retention.retention_tick``.
    Returns (y [R, n, d] float32, the pool)."""
    r, n, d = q.shape
    nkv = k.shape[1]
    g = n // nkv
    assert g < ROWS, f"{g} query heads a KV head do not fit a block of {ROWS}"
    big_d = ret.feature_dim(d)
    live, first, fresh = ret.tick_runs(slots, positions)
    flags = (live.astype(jnp.int32) | (first.astype(jnp.int32) << 1)
             | (fresh.astype(jnp.int32) << 2))
    blk = block_slots(slots.astype(jnp.int32), live)
    # [R, nkv, 8, d]: the group's query heads, zeros, the key last
    rows = jnp.concatenate([
        ret._group(q.astype(jnp.float32), nkv),
        jnp.zeros((r, nkv, ROWS - 1 - g, d), jnp.float32),
        k.astype(jnp.float32)[:, :, None, :]], axis=2)
    feat = ret.phi(rows)                                       # [R,nkv,8,D]
    vt = v.astype(jnp.float32)[..., None]                      # [R,nkv,d,1]
    dec = jnp.exp(log_decay.astype(jnp.float32))[..., None, None]

    row_spec = lambda *tail: pl.BlockSpec(              # noqa: E731
        (None, None) + tail, lambda h, i, *_: (i, h, 0, 0))
    pool_spec = lambda *tail: pl.BlockSpec(             # noqa: E731
        (None, None, None) + tail,
        lambda h, i, layer_ref, blk_ref, flag_ref:
            (layer_ref[0], blk_ref[i], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nkv, r),
        in_specs=[row_spec(ROWS, big_d), row_spec(d, 1), row_spec(1, 1),
                  pool_spec(d, big_d), pool_spec(1, big_d)],
        out_specs=[row_spec(ROWS, d), pool_spec(d, big_d),
                   pool_spec(1, big_d)],
    )
    # VMEM: the state's block in and out, two of each (the pipeline's),
    # the feature block, a step's [v, D] temporaries
    block = d * big_d * 4
    y, s, z = pl.pallas_call(
        functools.partial(_sweep_kernel, eps=ret.EPS),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, nkv, ROWS, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.z.shape, jnp.float32)],
        # operands count the scalar-prefetch ones: s is 6, z is 7
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(10 * block, 32 << 20)),
        interpret=interpret,
        name="retention_sweep",
    )(jnp.asarray(layer, jnp.int32).reshape(1), blk, flags,
      feat, vt, dec, state.s, state.z)
    return y[:, :, :g].reshape(r, n, d), ret.State(s, z)
