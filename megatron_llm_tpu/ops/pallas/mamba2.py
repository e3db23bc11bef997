"""The tick's state sweep for Mamba-2 (ops/mamba2.py): ONE kernel reads
each run's state once, takes the run's rows to it a TILE at a time, and
writes it once, in place.

Grid ``(block of heads, step)``, the steps innermost and in the rows'
order, as the power-retention and the gated-delta sweeps have their rows
(ops/pallas/retention.py, ops/pallas/gated_delta.py; the skeleton is
repeated here, not shared: a Mosaic kernel's payload carries its file's
source lines, so a sweep that moved into a common file would be a new
program to every compile cache that holds the others).  A STEP is a
SEGMENT of the tick's rows: the consecutive rows of one run (rows of one
sequence at consecutive positions, ``ops/retention.tick_runs``) inside one
tile of ``SWEEP_TILE`` rows of the row axis (``ops/mamba2.py``'s constant;
the tiles are cut on the row axis itself, so a run that starts mid-tile is
cut at the tile's end: :func:`sweep_plan`, from ``slots`` and ``positions``
alone).  The grid's second bound is the COUNT of the tick's segments, a
number the plan computes and the call takes as data: a dead row and a
row inside a segment are no grid step at all (a step that moves and
computes nothing still cost 0.15 us, a fifth of a millisecond over a
prompt tick's 23 layers).  A step's block of the state pool is its SLOT's
(a scalar-prefetched index), so while consecutive steps name one slot the
block stays where it is in VMEM; it is fetched when the slot changes and
written back when the next one is.  A run that starts a sequence
(``fresh``: position 0) takes a zero state whatever the slot held.  A dead
row is in no segment: nothing is moved or computed for it, and the caller's
select gives it a zero output.

A program holds ``HEADS`` heads' states side by side, ``[n, HEADS * p]``
float32 (1 MB at 128 x 32 x 64), the state size on the sublanes and heads x
width on the lanes, and takes a step to them one GROUP's lanes at a time
(the heads that share a B and a C):

* a segment of ONE row (a decode row; a run's single row in a tile) on the
  VPU: ``S <- dec S + B dtx^T`` is a sublane-broadcast row (the head's
  decay along its lanes) times the state plus a lane-broadcast column
  (``B``) times a sublane-broadcast row (``dt x``); ``y = S^T C`` a column
  times the state summed over the sublanes.  No MXU pass: ``C`` against a
  ``[128, 128]`` tile of the state would load the tile as the stationary
  operand for one useful row;
* a segment of MORE rows in Mamba-2's chunked (SSD) form on the resident
  state, ONE pass over it a segment where the row walk made one a row:
  ``y = (C S) exp(L) + (C B^T . exp(L_t - L_s), s <= t) dtx`` and ``S <-
  exp(L_end) S + B^T (dtx exp(L_end - L_s))``, with ``L`` the float32 sums
  of ``dt A`` over the segment's rows up to each row and ``E = L_end - L``
  the sums over the rows after it, both summed in the kernel as products
  of 0 / 1 masks with the tile's ``dt A`` (never a difference of two larger
  sums), so that every exponent is of a number that is <= 0 and no
  quotient of exponentials is taken (``dt`` has no clamp: a tile's rows can
  sum to -100).  The state is the MXU's
  stationary operand once for the segment's rows; the in-segment weights
  are built a head at a time and ``STACK`` heads' are multiplied in one
  product (the heads' weights side by side along the contraction, their
  ``dt x`` stacked under each other with the other heads' lanes zeroed), so
  that a product's contraction fills the MXU's 128.  Every product is
  float32 at ``highest`` (Mosaic's float32 dot at default precision is one
  bfloat16 pass).

HBM sees one read and one write of the state a run; a run of k rows costs
at most ``ceil(k / SWEEP_TILE) + 1`` passes over the state in VMEM, where the row
walk this kernel was until PR 54 made k.  On a v5e at the published widths
(64 heads of 64 in 8 groups, state 128; PERF.md section 6, PR 54) a decode
row's step is 3.2 us, the two 1 MB copies of its block; a row walked on a
resident block was 0.5 us (a 64-row run behind 32 decode rows 0.065 ms a
call); a segment in the chunked form is ~6 us whatever its rows (that run
0.028 ms): most of it the MXU taking the state's ``[128, 128]`` tiles as
its stationary operand six times over (``highest``) for 32 rows each, so a
longer tile wins nothing (64: 0.034 ms for that run, which the tick's row
64 cuts in two anyway) and a shorter one pays the loads more often (16:
0.041 ms).

The rows' operands come from the caller in the layouts the steps read, and
in as few arrays as that takes (every array the caller lays out is a device
operation a layer and tick: a profiler capture of the cell's 23 layers grew
by a sixth with one array a quantity, past what ``benchmark/lib/trace.py``
reads of a capture): ``[2, R, h * p]`` of ``dt x`` and the decay (a head's
scalar broadcast along its lanes); a program's ``B`` and ``C`` a row, as
COLUMNS for the one-row step (``[n, 2 * groups]``: group ``j``'s B in lane
``j``, its C in lane ``groups + j``; a static lane slice is a column) and
as ROWS ``[2 * groups, n]``, a tile's at a time, for the chunked step; and
``dt A`` as the caller has it, ``[R, h]``.  What else the chunked step
needs it makes of these in VMEM: ``B^T`` by products that contract the
rows, the decays by the products above, a head's decays along the lanes of
its sources by a product with ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.mamba2 import SWEEP_TILE
from megatron_llm_tpu.ops.retention import tick_runs

HEADS = 32        # heads of one program's block of the state
NAME = "mamba_sweep"
F32 = jnp.float32

_HI = dict(precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
_dot = functools.partial(jnp.dot, **_HI)


def _dot_nt(a, b):      # a b^T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), **_HI)


def _dot_tn(a, b):      # a^T b
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), **_HI)


def _sweep_kernel(layer_ref, word_ref,               # scalar prefetch
                  xd_ref, bc_ref, rows_ref, ld_ref, s_in,
                  y_ref, s_out, *, stack: int, hb: int):
    del layer_ref
    hh, i = pl.program_id(0), pl.program_id(1)
    flags = word_ref[1, i]
    live = (flags & 1) != 0
    first = (flags & 2) != 0
    fresh = (flags & 4) != 0
    many = (flags & 8) != 0
    lo, hi = word_ref[3, i] & 0xFFFF, word_ref[3, i] >> 16    # the tile's rows
    n, width = s_out.shape
    groups = bc_ref.shape[1] // 2
    lanes = width // groups
    t = ld_ref.shape[0]
    per = hb // groups                  # heads of one group in this block
    p = lanes // per

    def step(before):
        for j in range(groups):
            at = (slice(None), slice(j * lanes, (j + 1) * lanes))
            b_col = bc_ref[:, j:j + 1]                     # [n, 1]
            c_col = bc_ref[:, groups + j:groups + j + 1]
            dtx = xd_ref[0, pl.ds(lo, 1), at[1]]           # [1, lanes]
            dec = xd_ref[1, pl.ds(lo, 1), at[1]]
            s = before(at) * dec + b_col * dtx             # [n, lanes]
            s_out[at] = s
            y_ref[pl.ds(lo, 1), at[1]] = jnp.sum(s * c_col, axis=0,
                                                 keepdims=True)

    one = live & jnp.logical_not(many)

    @pl.when(one & fresh)
    def _():
        step(lambda at: jnp.zeros((n, lanes), F32))

    @pl.when(one & first & jnp.logical_not(fresh))
    def _():
        step(lambda at: s_in[at])

    @pl.when(one & jnp.logical_not(first))
    def _():
        step(lambda at: s_out[at])

    @pl.when(live & many)
    def _():
        iota = jax.lax.broadcasted_iota
        row = iota(jnp.int32, (t, 1), 0)
        mine = (row >= lo) & (row < hi)                    # [t, 1]
        # the decays of the segment's rows up to and with each row and of
        # the rows after it: float32 sums of what is <= 0, as 0 / 1 products
        col = iota(jnp.int32, (1, t), 1)
        both = mine & (col >= lo) & (col < hi)             # [t, t]
        ld = ld_ref[:, :hb]                                # [t, hb]: its heads
        for k in range(1, ld_ref.shape[1] // hb):
            ld = jnp.where(hh == k,
                           ld_ref[:, k * hb:(k + 1) * hb], ld)
        ld = jnp.where(mine, ld, 0.0)
        upto = _dot(jnp.where(both & (col <= row), 1.0, 0.0), ld)
        after = _dot(jnp.where(both & (col > row), 1.0, 0.0), ld)
        for j in range(groups):
            at = (slice(None), slice(j * lanes, (j + 1) * lanes))
            heads = slice(j * per, (j + 1) * per)
            s = jnp.where(fresh, 0.0, jnp.where(first, s_in[at], s_out[at]))
            y, s_out[at] = _segment(
                s, rows_ref[:, groups + j, :], rows_ref[:, j, :],
                xd_ref[0, :, at[1]], upto[:, heads], after[:, heads], lo, hi,
                stack=stack)
            y_ref[at] = jnp.where(mine, y, y_ref[at])


def _pick(x, e):
    """``x`` float32 against a 0 / 1 matrix ``e`` (bfloat16), to float32's
    own accuracy in three one-pass products: ``x`` is split into three
    bfloat16 parts, which ``e`` multiplies exactly."""
    out = jnp.zeros((x.shape[0], e.shape[1]), F32)
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        out = out + jnp.dot(part, e, preferred_element_type=F32)
        x = x - part.astype(F32)
    return out


@functools.partial(jax.jit, static_argnames=("stack",))
def _segment(s, c, b, dtx, upto, after, lo, hi, *, stack: int):
    """One group's heads through a segment in the chunked form, on values:
    the state ``s [n, lanes]`` before it, the tile's ``c``, ``b`` ``[t, n]``
    and ``dtx [t, lanes]``, the group's heads' decays ``upto``, ``after``
    ``[t, per]``, the segment's rows ``lo`` to ``hi`` of the tile.  Returns
    (y ``[t, lanes]``, the state after it).  A head's column goes along its
    lanes by a product with a 0 / 1 matrix (:func:`_pick`), not by a chain
    of selects a head: the kernel's body is lowered once a layer body of the
    program, and every equation of it is set-up time.  Jitted: a kernel's
    groups trace it once."""
    t, per = upto.shape
    lanes = s.shape[1]
    p = lanes // per
    w = stack * t
    iota = jax.lax.broadcasted_iota
    bf16 = jnp.bfloat16
    row = iota(jnp.int32, (t, 1), 0)
    mine = (row >= lo) & (row < hi)                        # [t, 1]
    # ``stack`` heads side by side along a product's contraction: lane u * t
    # + r is head u's source row r
    src = iota(jnp.int32, (1, w), 1) % t
    seen = (src <= row) & (src >= lo) & mine               # [t, w]
    head = iota(jnp.int32, (per, 1), 0)
    along = (head == iota(jnp.int32, (1, lanes), 1) // p).astype(bf16)
    under_of = iota(jnp.int32, (w, 1), 0) // t
    stacked = jnp.concatenate([upto] * stack, axis=0)      # [w, per]
    ones = jnp.ones((8, per), F32)

    b = jnp.where(mine, b, 0.0)
    dtx = jnp.where(mine, dtx, 0.0)
    spread = _pick(jnp.concatenate([upto, after], axis=0), along)
    big_l, to_end = spread[:t], jnp.exp(spread[t:])        # [t, lanes]
    carried = _dot(c, s) * jnp.exp(big_l)
    # [t, w]: C B^T, once a stacked head
    cb = _dot_nt(c, jnp.concatenate([b] * stack, axis=0))
    ys = []
    for q in range(per // stack):
        h0 = q * stack
        of = h0 + iota(jnp.int32, (1, w), 1) // t          # a lane's head
        # the decays up to the target row, and [1, w] up to source row r
        target = _pick(upto, (head == of).astype(bf16))
        source = _dot_nt(ones, jnp.where(
            iota(jnp.int32, (1, per), 1) == h0 + under_of, stacked,
            0.0))[0:1]
        weights = jnp.where(
            seen, jnp.exp(jnp.where(seen, target - source, 0.0)) * cb, 0.0)
        cut = slice(h0 * p, (h0 + stack) * p)
        under = jnp.where(                                 # [w, stack * p]
            under_of == iota(jnp.int32, (1, stack * p), 1) // p,
            jnp.concatenate([dtx[:, cut]] * stack, axis=0), 0.0)
        ys.append(carried[:, cut] + _dot(weights, under))
    whole = jnp.min(jnp.where(mine, big_l, 0.0), axis=0, keepdims=True)
    return (jnp.concatenate(ys, axis=1),
            s * jnp.exp(whole) + _dot_tn(b, dtx * to_end))


def sweep_blocks(heads: int, groups: int) -> int:
    """Heads of one program's block: ``HEADS`` or fewer, whole groups (or
    the one group's heads in equal parts)."""
    per = heads // groups
    hb = min(HEADS, heads)
    if hb >= per:
        return hb // per * per
    while per % hb:
        hb -= 1
    return hb


def stacked_heads(heads: int, groups: int) -> int:
    """Heads whose in-segment weights one product takes side by side: as
    many of a group's (of its part in a program's block) as fill a
    contraction of 128 with a tile's rows each."""
    hb = sweep_blocks(heads, groups)
    per = hb // max(1, hb * groups // heads)
    stack = max(1, min(per, 128 // SWEEP_TILE))
    while per % stack:
        stack -= 1
    return stack


def sweep_plan(slots, positions):
    """The steps of a tick's sweep, from the data the tick carries:
    ``(words [4, R] int32, count)``.  Step ``i`` is the tick's ``i``-th
    SEGMENT, ``words[:, i]`` its state slot, its flags (1 on, 2 the first of
    its run, 4 fresh, 8 more rows than one), the tile of the row axis it
    lies in, and its rows there (first | one past the last << 16); the
    words past ``count`` are read by no step.  ``count``, at least one (the
    one step of a tick with no live row is switched off), is the grid's
    bound and what ``ops/mamba2.sweep_steps`` counts on the host.  A segment
    starts at a run's first row and at a tile's first row."""
    tile = SWEEP_TILE
    r = slots.shape[0]
    i32 = jnp.int32
    live, first, fresh = tick_runs(slots, positions)
    rows = jnp.arange(r, dtype=i32)
    start = live & (first | (rows % tile == 0))
    # a live row's segment: the starts up to and with it, less one (a sum
    # over a triangle, not a scan: one fusion with what follows)
    seg = jnp.sum(start[None, :] & (rows[None, :] <= rows[:, None]), axis=1,
                  dtype=i32) - 1
    mine = seg[None, :] == rows[:, None]               # [step, row]
    size = jnp.sum(mine & live[None, :], axis=1, dtype=i32)

    def of_start(t):       # [R] -> [R]: of each step's first row
        return jnp.sum(jnp.where(mine & start[None, :], t.astype(i32)[None, :],
                                 0), axis=1)

    at = of_start(rows)
    flags = ((size > 0).astype(i32) | (of_start(first) << 1)
             | (of_start(fresh) << 2) | ((size > 1).astype(i32) << 3))
    words = jnp.stack([of_start(slots), flags, at // tile,
                       (at % tile) | ((at % tile + size) << 16)])
    return words, jnp.maximum(jnp.sum(start, dtype=i32), 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_sweep(x, dt, log_decay, b, c, pool: jax.Array, slots, positions,
                layer, *, interpret: bool = False):
    """The tick's rows against the layered pool ``[L, slots + 1, n, h *
    p]``, layer ``layer`` of it, in place.  Arguments as
    ``ops/mamba2.mamba_tick``.  Returns (y [R, h, p] float32 without the
    skip, the pool).  Jitted, so that a program's Mamba layers trace the
    kernel's unrolled body ONCE a shape and not once a layer body (a second
    each: twelve of them were 9 s of the cell's set-up)."""
    return planned_sweep(x, dt, log_decay, b, c, pool, slots, layer,
                         sweep_plan(slots, positions), interpret=interpret)


def planned_sweep(x, dt, log_decay, b, c, pool: jax.Array, slots, layer,
                  plan, *, interpret: bool = False):
    """:func:`mamba_sweep` by a ``plan`` of steps in :func:`sweep_plan`'s
    form (``tools/tpu_kernel_check.py --mamba`` hands one of its own beside:
    every live row a step, the walk this kernel was until PR 54)."""
    tile = SWEEP_TILE
    r, h, p = x.shape
    g, n = b.shape[1:]
    assert h % g == 0 and tile % 8 == 0
    hb = sweep_blocks(h, g)
    assert h % hb == 0
    nb = h // hb
    gb = max(1, hb * g // h)          # groups of one program's block
    stack = stacked_heads(h, g)
    nt = -(-r // tile)
    f32 = F32
    words, count = plan

    def rows_of(t, axis=0):    # the row axis to nt * tile, zeros behind
        pad = [(0, 0)] * t.ndim
        pad[axis] = (0, nt * tile - r)
        return jnp.pad(t, pad) if nt * tile > r else t

    xd = rows_of(jnp.stack([
        (dt.astype(f32)[..., None] * x.astype(f32)).reshape(r, h * p),
        jnp.broadcast_to(jnp.exp(log_decay.astype(f32))[..., None],
                         (r, h, p)).reshape(r, h * p)]), 1)    # [2,R',h*p]

    def block_groups(t):   # [R, g, n] -> [R, nb, gb, n]: a program's groups
        # a block inside ONE group (gb 1, several programs a group) reads
        # that group's
        t = jnp.repeat(t.astype(f32), max(1, nb // g), axis=1)
        return t.reshape(r, nb, gb, n)

    # a program's B and C a row: as ROWS ``[2 gb, n]`` for the chunked step
    # (a tile's at a time) and as COLUMNS for the one-row step
    rows = jnp.concatenate([block_groups(b), block_groups(c)], axis=2)
    bc = rows.transpose(0, 1, 3, 2)                            # [R,nb,n,2gb]
    rows = rows_of(rows)                                   # [R',nb,2gb,n]
    ld = rows_of(log_decay.astype(f32))                        # [R', h]

    lanes_spec = lambda *lead: pl.BlockSpec(                   # noqa: E731
        lead + (tile, hb * p),
        lambda hh, i, layer_ref, word_ref:
            (0,) * len(lead) + (word_ref[2, i], hh))
    col_spec = pl.BlockSpec(
        (None, None, n, 2 * gb),
        lambda hh, i, layer_ref, word_ref:
            (word_ref[2, i] * tile + (word_ref[3, i] & 0xFFFF), hh, 0, 0))
    rows_spec = pl.BlockSpec(
        (tile, None, 2 * gb, n),
        lambda hh, i, layer_ref, word_ref: (word_ref[2, i], hh, 0, 0))
    ld_spec = pl.BlockSpec(          # every head's: a program picks its own
        (tile, h), lambda hh, i, layer_ref, word_ref: (word_ref[2, i], 0))
    pool_spec = pl.BlockSpec(
        (None, None, n, hb * p),
        lambda hh, i, layer_ref, word_ref:
            (layer_ref[0], word_ref[0, i], 0, hh))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, count),                 # the tick's segments: data
        in_specs=[lanes_spec(2), col_spec, rows_spec, ld_spec, pool_spec],
        out_specs=[lanes_spec(), pool_spec],
    )
    block = n * hb * p * 4
    y, pool = pl.pallas_call(
        functools.partial(_sweep_kernel, stack=stack, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nt * tile, h * p), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        # operands count the scalar-prefetch ones: the pool is 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(8 * block, 32 << 20)),
        interpret=interpret,
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), words,
      xd, bc, rows, ld, pool)
    # a row in no segment was written by no step
    y = jnp.where((slots > 0)[:, None], y[:r], 0.0)
    return y.reshape(r, h, p), pool
