"""The paged Pallas kernels in interpret mode against the jnp gather path,
one small case a call: every pool dtype, mask, page size and head geometry
that tools/tpu_kernel_check.py compiles on the chip.

A file of its own beside tests/test_paged_kernel_walks.py (the
shared-walk scenarios and the bf16 operands' precision guard) and
tests/test_paged_engine.py (the engine): the tier-1 run hands a FILE to a
worker, and these cases take as long in interpret mode as everything else
there together.  The int8 / fp8 pools' cases, which take longer than all
of these, are in tests/test_paged_kernel_walks.py for the same reason.
"""

import jax.numpy as jnp
import pytest


def case_id(case) -> str:
    return "-".join(f"{k}{getattr(v, '__name__', v)}"
                    for k, v in case.items())


def check_case(case) -> None:
    """The kernel of every call shape == the gather path on one case."""
    from tools.tpu_kernel_check import max_err, paged_case

    # fp32 inputs: both sides are fp32 end to end and differ by reduction
    # order only; bf16 inputs (the quantized cases) round the output to
    # bf16, so one output ulp (2^-7 at |x| < 2) is the bound
    tol = 1e-5 if case.get("dtype") == jnp.float32 else 2e-2
    for name, (pallas_fn, jnp_fn) in paged_case(0, **case).items():
        assert max_err(pallas_fn(True), jnp_fn()) < tol, name


@pytest.mark.parametrize("case", [
    dict(n=4, nkv=2, d=128, page=8, dtype=jnp.float32),
    dict(n=4, nkv=2, d=128, page=8, dtype=jnp.float32, window=9),
    # the page walk (tools/tpu_kernel_check.py WALK_CASES): a context of
    # three 128-token blocks that ends inside the third, a horizon that is
    # no multiple of a block, a window that opens inside a block, rows
    # sharing a table beside dead rows (every ragged scenario)
    dict(n=4, nkv=2, d=128, page=16, dtype=jnp.float32, max_pages=24,
         context=300),
    dict(n=4, nkv=2, d=128, page=16, dtype=jnp.float32, max_pages=24,
         context=300, window=50),
    dict(n=4, nkv=2, d=128, page=8, dtype=jnp.float32, max_pages=48,
         context=300, window=150),
    dict(n=4, nkv=2, d=128, page=128, dtype=jnp.float32, max_pages=3,
         context=300),
    # 128 slots wide, 40 tokens of context, the tail names a NaN page:
    # nothing of it reaches the output, which is finite and the gather
    # path's
    dict(n=4, nkv=2, d=128, page=16, dtype=jnp.float32, max_pages=128,
         context=40, poison_tail=True),
    # Falcon-40B: 8 kv heads of 64, each head's key|value pair one
    # 128-lane operand
    dict(n=16, nkv=8, d=64, page=16, max_pages=24, context=300),
    # Falcon-7B (71 query heads on one kv head of 64: its key|value pair
    # is the 128-lane row) and Mistral-7B (32/8 x 128) head geometries
    dict(n=71, nkv=1, d=64, page=16, max_pages=24, context=300),
    dict(n=32, nkv=8, d=128, page=16, max_pages=24, context=300),
    # Command A+ (128/8 x 128) under its window with the tables of a
    # window page class: the slots behind the window name the null page,
    # so the first live page is not the table's first; and the same
    # geometry with no window (its full layers), the two masks of one tick
    dict(n=128, nkv=8, d=128, page=16, max_pages=24, context=300,
         window=100, slid_head=True),
    dict(n=128, nkv=8, d=128, page=16, max_pages=24, context=300),
    # SDAR-30B-A3B (32 query heads on 4 kv heads of 128: a group of 8 rows,
    # HALF a packed bf16 tile, on several kv heads): a slot's tile holds the
    # commit rows of the block before and the denoise rows of its block on
    # one table (the ``blocks`` call shape), in both dtypes
    dict(n=32, nkv=4, d=128, page=16, max_pages=24, context=300, block=4),
    dict(n=32, nkv=4, d=128, page=16, dtype=jnp.float32, max_pages=24,
         context=300, block=4),
    # LFM2-24B-A2B (32 / 8 x 64): a group of 4 padded to 8 rows and a head
    # of 64 read as its 128-lane pair, on several kv heads
    dict(n=32, nkv=8, d=64, page=16, max_pages=24, context=300, block=4),
    # Ouro-2.6B (16 query heads, a K/V head each: a group of ONE padded to 8
    # rows): decode rows of two and three blocks, each walk's first block
    # started by the walk before it
    dict(n=16, nkv=16, d=128, page=16, max_pages=24, context=300),
    # rows that fill no whole number of tiles: 19, the wrapper pads dead
    # ones behind them and slices them off again
    dict(n=32, nkv=4, d=128, page=16, dtype=jnp.float32, max_pages=24,
         context=300, block=4, tail=3),
], ids=case_id)
def test_paged_kernels_interpret_match_jnp_path(case):
    """The Pallas decode / prefill / ragged kernel (interpret mode) == the
    jnp gather path on plain pools, with and without a sliding window —
    the scenarios tools/tpu_kernel_check.py compiles on the chip."""
    check_case(case)


def test_paged_call_holds_no_transpose_where_nothing_is_padded():
    """Where a group is whole sublane tiles and a head whole lanes (``g ==
    gp``, ``w == d``: SDAR 8 x 128, Command A+ 16 x 128) the kernel takes
    the query and gives the output as the layer holds them: the operand of
    ``pallas_call`` and its result are ``[R, n, d]``, and no ``transpose``
    stands outside it."""
    import jax

    from megatron_llm_tpu.ops.pallas import paged_attention as pk

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)

    r, d, page = 24, 128, 16
    S = jax.ShapeDtypeStruct
    for n, nkv in ((32, 4), (128, 8)):
        jaxpr = jax.make_jaxpr(lambda *a: pk.paged_ragged_kernel(
            *a, scale=1.0))(
                S((r, 1, n, d), jnp.bfloat16),
                S((40, page, 2 * nkv * d), jnp.bfloat16),
                S((3, 12), jnp.int32), *(S((r,), jnp.int32),) * 3)
        eqns = list(primitives(jaxpr.jaxpr))
        assert not [e for e in eqns if e.primitive.name == "transpose"]
        call, = (e for e in eqns if e.primitive.name == "pallas_call")
        assert (r, n, d) in [v.aval.shape for v in call.invars]
        assert [v.aval.shape for v in call.outvars] == [(r, n, d)]
