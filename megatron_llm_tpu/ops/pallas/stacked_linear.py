"""A GLU ``fc1`` GEMM that reads its layer of the STACK where it lies.

A GLU ``fc1`` leaf is stored ``[L, h, 2, ffn]`` (value half at ``[:, 0]``,
gated half at ``[:, 1]``: models/transformer.py ``init_layer_params``), and
a bf16 array whose second-minor axis is 2 lies on the chip in tiles of
``2 x 128`` with the two rows of a column packed into one 32-bit word:
linear ``[L, h, ffn]`` words of (value, gate) pairs.  The contraction axis
``h`` is OUTSIDE the tile, so no dot can take the leaf as its operand; XLA
slices the layer out of the stack and writes it again in a GEMM layout
before it multiplies, four passes over the layer's bytes for the one the
GEMM needs (PERF.md section 6, PR 42: 2.1 ms of moving for 0.4 ms of GEMM
a layer at 5120 x 2 x 17408 and 40 rows).

This kernel takes the whole leaf as its operand (no slice, no copy: the
layer is a scalar-prefetched block index) and streams ``[tk, 2, tn]``
blocks of the layer.  In VMEM a block is ``tk x tn/128`` rows of 128
words, a row of ``h`` after the other; a GEMM operand wants eight
consecutive ``h`` under each other in a tile.  A STRIDED load does that on
the way into registers: the words of columns ``[128 c, 128 c + 128)`` are
every ``tn/128``-th row from row ``c`` on, ``[tk, 128]`` with ``h`` along
the sublanes (reading the block whole and re-tiling it in registers took
three times as long: 1.24 against 0.57 ms a layer at 5120 x 2 x 17408,
where the bytes alone take 0.44; PERF.md section 6, PR 42).  A word is
then split in registers: a bf16 value IS the upper half of the float32 of
the same number, so ``word << 16`` and ``word & 0xffff0000`` are the value
and the gate as float32, exactly.  Two MXU dots a column group, float32
accumulators across the ``h`` blocks, ``x.dtype`` out.

``interpret=True`` runs the same program on the CPU (tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 256                 # rows of one program's block of ``x``
BLOCK_WORDS = 512 * 1024   # (value, gate) words of one weight block: 2 MB


def _divisor(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; 0 where there is none."""
    return max((t for t in range(128, min(dim, cap) + 1, 128)
                if dim % t == 0), default=0)


def tiles(h: int, ffn: int) -> Optional[Tuple[int, int]]:
    """``(tk, tn)`` of a weight block, or None where the widths are off
    the 128-lane grid."""
    tn = _divisor(ffn, 1024)
    tk = _divisor(h, BLOCK_WORDS // tn) if tn else 0
    return (tk, tn) if tk else None


def refusal(x: jax.Array, stack: jax.Array) -> Optional[str]:
    """Why this kernel cannot take ``x @ stack[layer]``, or None."""
    if stack.dtype != jnp.bfloat16 or x.dtype != jnp.bfloat16:
        return (f"{stack.dtype} weights under {x.dtype} rows: a word is "
                "split into two bfloat16 halves")
    if stack.ndim != 4 or stack.shape[2] != 2:
        return f"a leaf of shape {stack.shape} is not [L, h, 2, ffn]"
    if tiles(stack.shape[1], stack.shape[3]) is None:
        return (f"widths {stack.shape[1]} x {stack.shape[3]} are not whole "
                "128-lane groups")
    return None


def _kernel(layer_ref, x_ref, w_ref, value_ref, gate_ref, acc_v, acc_g, *,
            k_blocks: int):
    del layer_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_v[...] = jnp.zeros_like(acc_v)
        acc_g[...] = jnp.zeros_like(acc_g)

    # [1, tk, 2, tn] bfloat16 as words: the pair of a column is one word,
    # the value in its low half; as rows of 128 words, row k * groups + c
    # holds columns [128 c, 128 c + 128) of h = k
    tk, tn = w_ref.shape[1], w_ref.shape[3]
    groups = tn // 128
    rows = w_ref.bitcast(jnp.uint32).reshape(tk * groups, 128)
    x = x_ref[...]
    for c in range(groups):
        cols = slice(128 * c, 128 * (c + 1))
        words = rows[pl.ds(c, tk, stride=groups), :]           # [tk, 128]
        value = jax.lax.bitcast_convert_type(
            words << 16, jnp.float32).astype(jnp.bfloat16)
        gate = jax.lax.bitcast_convert_type(
            words & jnp.uint32(0xFFFF0000), jnp.float32).astype(jnp.bfloat16)
        acc_v[:, cols] += jnp.dot(x, value,
                                  preferred_element_type=jnp.float32)
        acc_g[:, cols] += jnp.dot(x, gate,
                                  preferred_element_type=jnp.float32)

    @pl.when(k == k_blocks - 1)
    def _():
        value_ref[...] = acc_v[...].astype(value_ref.dtype)
        gate_ref[...] = acc_g[...].astype(gate_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def glu_stack_matmul(x: jax.Array, stack: jax.Array, layer: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """``x [rows, h] @ stack[layer] [h, 2, ffn] -> [rows, 2, ffn]`` with
    the stack ``[L, h, 2, ffn]`` read in place (see the module's
    docstring; ``refusal(x, stack)`` must be None)."""
    assert refusal(x, stack) is None, refusal(x, stack)
    rows, h = x.shape
    ffn = stack.shape[3]
    tk, tn = tiles(h, ffn)
    # whole packed sublanes of bfloat16 rows; beyond ROWS, blocks of ROWS
    tm = ROWS if rows > ROWS else -(-rows // 16) * 16
    pad = -rows % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    m = rows + pad
    out = jax.ShapeDtypeStruct((m, ffn), x.dtype)
    value, gate = pl.pallas_call(
        functools.partial(_kernel, k_blocks=h // tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // tm, ffn // tn, h // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, k, layer: (i, k)),
                pl.BlockSpec((1, tk, 2, tn),
                             lambda i, j, k, layer: (layer[0], k, 0, j)),
            ],
            out_specs=[pl.BlockSpec(
                (tm, tn), lambda i, j, k, layer: (i, j))] * 2,
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * 2),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="glu_stack_matmul",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, stack)
    return jnp.stack([value[:rows], gate[:rows]], axis=1)
