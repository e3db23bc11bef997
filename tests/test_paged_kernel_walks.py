"""The paged kernel's shared walk and its bf16 operands, in interpret mode:
a run of consecutive rows of one sequence walked once against the gather
path and against the one-row walk, and the guard of the kernel's precision,
at the serving configurations' head geometries; and the small cases of
tests/test_paged_kernel_cases.py on QUANTIZED pools (int8 and fp8 pages
with their per-page scales).

A file of its own beside tests/test_paged_engine.py (the engine, the pool,
the sampler) and tests/test_paged_kernel_cases.py (one small case a call,
plain pools).  The tier-1 run hands a FILE to a worker, files of the MOST
cases first (pytest-xdist 3.8, ``--dist loadfile``), so what takes longest
has to be the file of the most cases or it starts last and bounds the run:
the fifty walk cases, which share their kernel calls (``_run_outputs``),
take about three quarters of what tests/test_paged_engine.py took with
them, and the six quantized cases (40-90 s each beside five other workers)
took longer than the twelve plain ones they stood among.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_paged_kernel_cases import case_id, check_case
from tools import tpu_kernel_check as kernel_check

# the serving configurations' head geometries (tools/tpu_kernel_check.py),
# the latent row of MLA (one head of 640 lanes, 32 query heads) and one
# quantized pool
RUN_GEOMETRIES = {
    "falcon": kernel_check.FALCON,
    "mistral": kernel_check.MISTRAL,
    "commanda": kernel_check.COMMANDA,
    "latent": kernel_check.LATENT,
    "mistral-int8": dict(kernel_check.MISTRAL, kv_dtype="int8"),
}
RUN_SCENARIOS = {"tiles": False, "inside": False, "blocks": False,
                 "verify": False, "window": True, "window_inside": True}
# the bf16 operands' precision test holds both storage dtypes of a
# quantized pool: int8 on the paired layout (above) and fp8 on the pair of
# 64s read whole
GEOMETRIES = {**RUN_GEOMETRIES,
              "falcon-fp8": dict(kernel_check.FALCON, kv_dtype="fp8")}


@functools.lru_cache(maxsize=None)
def _run_outputs(geometry: str, window: bool, fp32: bool = False):
    """The kernel's and the gather path's outputs of one ``run_case`` call
    (and at float32 the one-row walk's), made once for the scenarios that
    share the call."""
    pallas_fn, jnp_fn, scenarios = kernel_check.run_case(
        3, window=window, **GEOMETRIES[geometry],
        **(dict(dtype=jnp.float32) if fp32 else {}))
    return (pallas_fn(True), jnp_fn(),
            pallas_fn(True, spread=True) if fp32 else None, scenarios)


@pytest.mark.parametrize("scenario", RUN_SCENARIOS)
@pytest.mark.parametrize("geometry", RUN_GEOMETRIES)
def test_paged_kernel_shared_walk_matches_jnp_path(geometry, scenario):
    """A run of consecutive rows of one sequence, walked once (interpret
    mode), == the gather path, row for row: a run that fills whole tiles,
    one inside a tile beside another request's row and dead rows, one
    across a compute-block boundary with two horizons, a verify block among
    decode rows, and under a window with slid tables a run whose first rows
    see a page its last rows do not."""
    out, ref, _, scenarios = _run_outputs(geometry, RUN_SCENARIOS[scenario])
    rows = scenarios[scenario]
    assert kernel_check.max_err(out[rows], ref[rows]) < 2e-2
    dead = np.setdiff1d(np.arange(out.shape[0]),
                        np.concatenate(list(scenarios.values())))
    assert not np.asarray(out[dead]).any(), "a dead row writes zeros"


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize(
    "geometry", [g for g in RUN_GEOMETRIES if "int8" not in g])
def test_paged_kernel_shared_walk_is_the_one_row_walk(geometry, window):
    """At float32 a row's result from the shared walk is what the one-row
    walk gives (every row launched in a tile of its own): the same keys in
    the same blocks, so reduction order within a matmul is all that
    differs; and both are the gather path's."""
    out, ref, alone, scenarios = _run_outputs(geometry, window, fp32=True)
    live = np.concatenate(list(scenarios.values()))
    assert kernel_check.max_err(out[live], alone[live]) < 1e-6
    assert kernel_check.max_err(out[live], ref[live]) < 1e-5


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_paged_kernel_bf16_operands_round_nothing(geometry, window):
    """THE GUARD OF THE KERNEL'S PRECISION.  On bf16 queries and bf16 pages
    both matmuls take bf16 operands (one MXU pass for the scores, two for
    the values).  That must change no number: the output is what a float32
    computation on the SAME bf16 values gives, rounded ONCE to bf16 — under
    1% of the elements differ (the order of float32 sums at a rounding
    boundary), none by more than one bf16 unit in the last place.  In
    interpret mode on the CPU a bf16 dot with float32 accumulation is
    exact, so what this measures is the probabilities' split into two bf16
    halves: a plain ``p.astype(bfloat16)`` in front of the value matmul
    passes the 2e-2 of the tests above and FAILS here (tens of percent of
    the elements move: the test below).  The int8 and fp8 pools are held to
    the same rule against their dequantized float32 form: a quantized value
    is exact in bf16, and the per-page scales multiply the float32 scores
    and the float32 probabilities (before their split)."""
    out, _, _, scenarios = _run_outputs(geometry, window)
    _, jnp_fn, _ = kernel_check.run_case(
        3, window=window, **GEOMETRIES[geometry])
    assert out.dtype == jnp.bfloat16
    live = np.concatenate(list(scenarios.values()))
    differ, ulps = kernel_check.bf16_ulps(
        out[live], jnp_fn(exact=True)[live])
    assert differ < 0.01 and ulps <= 1.0, (differ, ulps)


@pytest.mark.parametrize("case", [
    dict(n=4, nkv=4, d=128, page=16, kv_dtype="int8"),
    dict(n=4, nkv=1, d=64, page=8, kv_dtype="fp8", window=20),
    # 128 slots wide, 40 tokens of context, the tail names a NaN page
    dict(n=4, nkv=1, d=64, page=16, kv_dtype="int8", max_pages=128,
         context=40, poison_tail=True),
    # Falcon-40B (8 kv heads of 64), Falcon-7B (71 query heads on one kv
    # head of 64) and Mistral-7B (32/8 x 128) head geometries
    dict(n=16, nkv=8, d=64, page=16, kv_dtype="int8", max_pages=24,
         context=300, window=100),
    dict(n=71, nkv=1, d=64, page=16, kv_dtype="int8", max_pages=24,
         context=300, window=100),
    dict(n=32, nkv=8, d=128, page=16, kv_dtype="fp8", max_pages=24,
         context=300, window=50),
], ids=case_id)
def test_paged_kernels_interpret_match_jnp_path(case):
    """The Pallas decode / prefill / ragged kernel (interpret mode) == the
    jnp gather path on int8 / fp8 pools, with and without a sliding
    window."""
    check_case(case)
