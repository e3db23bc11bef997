"""The ``delta_sweep`` kernel (ops/pallas/gated_delta.py) in interpret mode
against the tick's ``jnp`` form (ops/gated_delta.delta_tick), one small
case a call: the bits the engine's tests cannot reach on the CPU, where the
tick takes the ``jnp`` form.  Kept apart from tests/test_gigachat35.py so
that neither file is a worker's long pole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.ops import gated_delta as gd
from megatron_llm_tpu.ops.pallas import gated_delta as kernel
from megatron_llm_tpu.ops.pallas.retention import block_slots
from megatron_llm_tpu.ops.retention import tick_runs

# (slot, first position, rows) a span, in the tick's order; slot 0: dead.
# A slot's rows of one tick are ONE run, as the engine packs them (a second
# run of a slot whose block is still resident would read the block as it
# was fetched: the retention sweep's contract too)
TICKS = {
    "decode_rows": [(1, 5, 1), (2, 0, 1), (3, 9, 1), (4, 2, 1)],
    "one_prompt_run": [(2, 0, 12)],
    "a_run_that_goes_on": [(3, 7, 9)],
    "decode_then_prompt": [(1, 3, 1), (4, 0, 6), (2, 8, 1)],
    "dead_rows_between": [(0, 0, 2), (3, 4, 3), (0, 0, 1), (1, 0, 2),
                          (0, 0, 3)],
    "all_dead": [(0, 0, 4)],
    "two_prompts_and_the_slots": [(1, 0, 5), (2, 6, 5), (3, 1, 1),
                                  (4, 0, 1)],
}
HEADS = {"one_block": (2, 4, 16, 16), "two_blocks": (8, 32, 8, 128),
         "a_key_head_a_value_head": (4, 4, 16, 16)}


def _case(spans, hk, hv, dk, dv, seed=0):
    slots = np.concatenate([np.full(n, s) for s, _, n in spans])
    pos = np.concatenate([np.arange(p, p + n) if s else np.zeros(n, int)
                          for s, p, n in spans])
    r = len(slots)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gd.l2_normalize(jax.random.normal(ks[0], (r, hk, dk))) * dk ** -0.5
    k = gd.l2_normalize(jax.random.normal(ks[1], (r, hk, dk)))
    v = jax.random.normal(ks[2], (r, hv, dv))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (r, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, hv)))
    pool = jax.random.normal(ks[5], (2, 5, hv, dk, dv))
    return (q, k, v, g, beta, pool, jnp.asarray(slots, jnp.int32),
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("case", list(TICKS))
def test_kernel_matches_the_jnp_tick(case, heads):
    args = _case(TICKS[case], *HEADS[heads])
    want_o, want_pool = gd.delta_tick(*args, 1)
    got_o, got_pool = kernel.delta_sweep(*args, 1, interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    slots = np.asarray(args[6])
    live = sorted(set(slots[slots > 0].tolist()))
    np.testing.assert_allclose(got_pool[1, live], want_pool[1, live],
                               atol=2e-5)
    # the other layer, and the slots no live row named (but the null
    # slot, which dead rows may rewrite), are bit for bit what they were
    np.testing.assert_array_equal(got_pool[0], args[5][0])
    idle = [s for s in range(1, 5) if s not in live]
    np.testing.assert_array_equal(got_pool[1, idle], args[5][1, idle])


def test_a_fresh_run_takes_zero_whatever_the_slot_held():
    args = _case([(2, 0, 3)], 2, 4, 16, 16)
    noisy, _ = kernel.delta_sweep(*args, 0, interpret=True)
    clean, _ = kernel.delta_sweep(*args[:5], jnp.zeros_like(args[5]),
                                  *args[6:], 0, interpret=True)
    np.testing.assert_array_equal(noisy, clean)
    # ... and a run that goes on does not
    args = _case([(2, 1, 3)], 2, 4, 16, 16)
    noisy, _ = kernel.delta_sweep(*args, 0, interpret=True)
    clean, _ = kernel.delta_sweep(*args[:5], jnp.zeros_like(args[5]),
                                  *args[6:], 0, interpret=True)
    assert np.abs(np.asarray(noisy) - np.asarray(clean)).max() > 1e-3


def test_a_dead_row_names_the_block_of_the_live_row_before_it():
    """What keeps a dead row from moving a block of the pool."""
    slots = jnp.asarray([0, 0, 3, 3, 0, 1, 0], jnp.int32)
    live, _, _ = tick_runs(slots, jnp.arange(7, dtype=jnp.int32))
    assert block_slots(slots, live).tolist() == [3, 3, 3, 3, 3, 1, 1]
    assert kernel.NAME == "delta_sweep" and kernel.HEADS == 16
