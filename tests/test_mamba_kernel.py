"""The Mamba-2 state sweep (``ops/pallas/mamba2.py`` ``mamba_sweep``) in
interpret mode on the CPU against the tick's ``jnp`` form
(``ops/mamba2.mamba_tick``), over the ticks of tests/test_nemotron_h.py
(``TICKS``), and the host's count of its steps against the kernel's own
plan.  Kept apart from tests/test_nemotron_h.py so that the two files run on
two workers (an interpreted case is 2-12 s)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.ops import mamba2 as mb
from megatron_llm_tpu.ops.pallas import mamba2 as kernel
from tests.test_nemotron_h import TICKS, T, _rows, _tick_rows
from tools.tpu_kernel_check import mamba_walk_plan


@pytest.mark.parametrize("heads,groups", [(8, 2), (64, 8), (64, 1), (4, 4)])
@pytest.mark.parametrize("name", list(TICKS))
def test_the_sweep_kernel_is_the_ticks_form(name, heads, groups):
    """``mamba_sweep`` in interpret mode against ``mamba_tick``: outputs,
    the touched slots' states, and every other slot's bits; a program's
    block whole groups (8 heads in 2; the published 64 in 8: four groups a
    block), parts of one group (64 in 1) and a group a head (4 in 4)."""
    assert kernel.NAME == "mamba_sweep"
    assert kernel.sweep_blocks(64, 8) == 32 and kernel.sweep_blocks(8, 2) == 8
    x, dt, ld, b, c, slots, pos = _tick_rows(name, h=heads, p=16, g=groups,
                                             n=16)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 16, heads * 16))
    want_y, want = mb.mamba_tick(x, dt, ld, b, c, pool, slots, pos, layer=1)
    y, new = kernel.mamba_sweep(x, dt, ld, b, c, pool, slots, pos, 1,
                                interpret=True)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-4)
    np.testing.assert_allclose(new[:, 1:], want[:, 1:], rtol=0, atol=2e-4)
    idle = [s for s in range(1, 5) if s not in np.asarray(slots)]
    np.testing.assert_array_equal(new[1, idle], pool[1, idle])
    np.testing.assert_array_equal(new[0], pool[0])


def test_a_tiles_decays_far_below_float32s_range_stay_finite():
    """``dt`` has no clamp and ``A`` reaches -16: a tile's rows whose ``dt
    A`` sum below -80 (``exp`` of the sum underflows, a quotient of two such
    would be 0 / 0) give finite outputs equal to the recurrence, in the
    tick's ``jnp`` form and in the kernel."""
    rows = 2 * T - 2
    x, _, _, b, c = (t[0] for t in _rows(3, 1, rows, h=8, p=16, g=2, n=16))
    dt = 0.25 + jax.random.uniform(jax.random.PRNGKey(8), (rows, 8))
    ld = -dt * jnp.linspace(4.0, 16.0, 8)
    assert float(ld[:T].sum(0).max()) < -80 and float(ld.sum(0).min()) < -500
    slots = jnp.asarray([2] + [3] * (rows - 1), jnp.int32)
    pos = jnp.asarray([5] + list(range(40, 40 + rows - 1)), jnp.int32)
    pool = jax.random.normal(jax.random.PRNGKey(5), (1, 5, 16, 8 * 16))
    want, last = mb.mamba_recurrent(
        *(t[None, 1:] for t in (x, dt, ld, b, c)),
        s0=pool[0, 3].reshape(1, 16, 8, 16))
    for form in (mb.mamba_tick,
                 functools.partial(kernel.mamba_sweep, interpret=True)):
        y, new = form(x, dt, ld, b, c, pool, slots, pos, 0)
        assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(new).all())
        np.testing.assert_allclose(y[1:], want[0], rtol=0, atol=2e-4)
        np.testing.assert_allclose(new[0, 3].reshape(16, 8, 16), last[0],
                                   rtol=0, atol=2e-4)


@pytest.mark.parametrize("gone", [None, 3], ids=["as packed", "a row gone"])
@pytest.mark.parametrize("name", list(TICKS))
def test_the_hosts_count_of_steps_is_the_kernels(name, gone):
    """``sweep_steps`` (numpy, what ``mlt_engine_state_steps_total`` adds a
    tick) against the kernel's own plan: the steps it switches on, one a
    segment; a decode row one, a run its tiles.  ``gone``: with that row
    dead (a run it lay in is two runs, or starts a row later)."""
    *_, slots, pos = _tick_rows(name)
    if gone is not None:
        slots = slots.at[gone].set(0)
    words, count = kernel.sweep_plan(slots, pos)
    _, flags, _, span = np.asarray(words)
    steps = mb.sweep_steps(slots, pos)
    assert max(steps, 1) == int(count) and steps == int((flags & 1).sum())
    live = int((np.asarray(slots) > 0).sum())
    span = span[:steps]
    assert ((span >> 16) - (span & 0xFFFF)).sum() == live
    assert steps <= live
    if gone is not None:
        return
    runs = [r for s, _, r in TICKS[name] if s]
    assert steps <= sum(-(-r // T) + 1 for r in runs)
    if name == "a run of a tile's rows":
        assert steps == 1
    if name.startswith("two runs whose boundary"):
        assert steps == 3                            # 24, then 8 and 32


@pytest.mark.parametrize("name", [
    "decode rows, a prompt run from 0, a dead row, a run that goes on",
    "two runs whose boundary falls inside a tile (the cell's 24 then 40)"])
def test_a_plan_of_one_row_steps_is_the_same_numbers(name):
    """Every live row a step of its own (the walk the kernel was until PR
    54, the plan ``tools/tpu_kernel_check.py --mamba`` times beside the
    tiles) gives the tick's form too: a run's later rows take the one-row
    step on the state the step before left, as the row behind a full tile
    does."""
    x, dt, ld, b, c, slots, pos = _tick_rows(name, h=8, p=16, g=2, n=16)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 16, 8 * 16))
    want_y, want = mb.mamba_tick(x, dt, ld, b, c, pool, slots, pos, layer=1)
    plan = mamba_walk_plan(slots, pos)
    assert int(plan[1]) == int((np.asarray(slots) > 0).sum())
    y, new = kernel.planned_sweep(x, dt, ld, b, c, pool, slots, 1, plan,
                                  interpret=True)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-4)
    np.testing.assert_allclose(new[:, 1:], want[:, 1:], rtol=0, atol=2e-4)
