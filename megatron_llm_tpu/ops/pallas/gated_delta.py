"""The tick's state sweep for the gated delta rule (ops/gated_delta.py):
ONE kernel reads each run's state once, walks the run's rows on it, and
writes it once, in place.

Grid ``(block of value heads, row)``, the rows innermost and in order, as
the power-retention sweep has it (ops/pallas/retention.py): a row's block
of the state pool is its SLOT's (a scalar-prefetched index), so while
consecutive rows name one slot (a run: rows of one sequence at consecutive
positions) the block stays where it is in VMEM; it is fetched when the slot
changes and written back when the next one is.  A decode row is a run of
one.  A dead row names the slot of the live row before it, moves nothing
and computes nothing.  A run that starts a sequence (``fresh``: position
0) takes a zero state whatever the slot held.

A program holds ``HEADS`` value heads' states, ``[HEADS, dk, dv]`` float32
(1 MB at 16 x 128 x 128), keys on sublanes and values on lanes, and walks
them head by head on the VPU: ``S <- exp(g) S``; the read ``S^T k`` is a
lane-broadcast column times the state summed over sublanes; the write
``k u^T`` a column times a sublane-broadcast row; ``o = S^T q`` as the
read.  An MXU pass a head and row would load the ``[128, 128]`` state as
the stationary operand for 2 useful rows: ~6x the VPU's cost at
``highest``.  HBM sees one read and one write of the state a run; what a
prompt run pays more is VMEM passes.

The rows' operands come from the caller in the layouts the steps read: the
queries and keys TRANSPOSED, ``[dk, 2 * HEADS]`` a program (a head's query
in lane ``j``, its key in lane ``HEADS + j``: a static lane slice is a
column), and ``[3, HEADS, dv]`` of values, decays and write strengths, the
two scalars a head broadcast along its lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas.retention import block_slots
from megatron_llm_tpu.ops.retention import tick_runs

HEADS = 16        # value heads of one program's block of the state
NAME = "delta_sweep"


def _sweep_kernel(layer_ref, blk_ref, flag_ref,      # scalar prefetch
                  qk_ref, vgb_ref, s_in, y_ref, s_out):
    del layer_ref, blk_ref
    row = pl.program_id(1)
    flags = flag_ref[row]
    live = (flags & 1) != 0
    first = (flags & 2) != 0
    fresh = (flags & 4) != 0
    heads = s_out.shape[0]

    def step(before):
        qk = qk_ref[...]                                   # [dk, 2 * heads]
        for j in range(heads):
            v = vgb_ref[0, j:j + 1, :]                     # [1, dv]
            dec = vgb_ref[1, j:j + 1, :]
            beta = vgb_ref[2, j:j + 1, :]
            q_col = qk[:, j:j + 1]                         # [dk, 1]
            k_col = qk[:, heads + j:heads + j + 1]
            s = before(j) * dec
            read = jnp.sum(s * k_col, axis=0, keepdims=True)
            s = s + k_col * (beta * (v - read))
            s_out[j] = s
            y_ref[j:j + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True)

    @pl.when(live & fresh)
    def _():
        step(lambda j: jnp.zeros(s_out.shape[1:], jnp.float32))

    @pl.when(live & first & jnp.logical_not(fresh))
    def _():
        step(lambda j: s_in[j])

    @pl.when(live & jnp.logical_not(first))
    def _():
        step(lambda j: s_out[j])

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)


def delta_sweep(q, k, v, g, beta, pool: jax.Array, slots, positions, layer,
                *, interpret: bool = False):
    """The tick's rows against the layered pool ``[L, slots + 1, hv, dk,
    dv]``, layer ``layer`` of it, in place.  Arguments as
    ``ops/gated_delta.delta_tick``.  Returns (o [R, hv, dv] float32, the
    pool)."""
    r, hk, dk = q.shape
    hv, dv = v.shape[1:]
    hb = min(HEADS, hv)
    assert hv % hb == 0 and hv % hk == 0
    nb = hv // hb
    live, first, fresh = tick_runs(slots, positions)
    flags = (live.astype(jnp.int32) | (first.astype(jnp.int32) << 1)
             | (fresh.astype(jnp.int32) << 2))
    blk = block_slots(slots.astype(jnp.int32), live)
    f32 = jnp.float32

    def cols(t):      # [R, hk, dk] -> [R, nb, dk, hb]: a value head a lane
        t = jnp.repeat(t.astype(f32), hv // hk, axis=1)
        return t.reshape(r, nb, hb, dk).transpose(0, 1, 3, 2)

    qk = jnp.concatenate([cols(q), cols(k)], axis=-1)      # [R,nb,dk,2hb]
    lanes = lambda t: jnp.broadcast_to(                    # noqa: E731
        t.astype(f32)[..., None], (r, hv, dv))
    vgb = jnp.stack([v.astype(f32), lanes(jnp.exp(g.astype(f32))),
                     lanes(beta)], axis=1)                 # [R,3,hv,dv]
    vgb = vgb.reshape(r, 3, nb, hb, dv).transpose(0, 2, 1, 3, 4)

    row_spec = lambda *tail: pl.BlockSpec(                 # noqa: E731
        (None, None) + tail,
        lambda h, i, *_: (i, h) + (0,) * len(tail))
    pool_spec = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda h, i, layer_ref, blk_ref, flag_ref:
            (layer_ref[0], blk_ref[i], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, r),
        in_specs=[row_spec(dk, 2 * hb), row_spec(3, hb, dv), pool_spec],
        out_specs=[row_spec(hb, dv), pool_spec],
    )
    block = hb * dk * dv * 4
    y, pool = pl.pallas_call(
        _sweep_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, nb, hb, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        # operands count the scalar-prefetch ones: the pool is 5
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(8 * block, 32 << 20)),
        interpret=interpret,
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), blk, flags, qk, vgb, pool)
    return y.reshape(r, hv, dv), pool
