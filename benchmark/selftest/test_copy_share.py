"""The reader of `copy_share.batch` (PR 42) on a trace made by hand: which
operations count as moving and which do not, that only the tick program's
executions are read, and the three answers (a number, 0.0, nothing)."""

import os
import types

from benchmark.lib import cells, trace

HERE = os.path.dirname(os.path.abspath(__file__))
READER = cells.Cell.reader_at(os.path.join(
    os.path.dirname(HERE), "layer_metrics", "copy_share.batch.py"))
MS = 1e6   # ns


def _op(text, start_ms, dur_ms, op_name=""):
    op = trace.Op(text, start_ms * MS, (start_ms + dur_ms) * MS)
    op.op_name = op_name
    return op


def _run(ops, modules):
    dev = trace.Device(0, ops, [(n, s * MS, e * MS) for n, s, e in modules])
    return types.SimpleNamespace(trace=trace.Reduced([dev]))


TICK = ("jit_tick(123)", 0.0, 100.0)
MOVING = [   # 10 + 8 + 2 + 4 + 1 = 25 ms
    _op("%copy.108 = bf16[1,5120,2,17408]{1,3,2,0} copy(%x)", 0, 10,
        "jit(tick)/decode-fwd/while/body/dynamic_slice"),
    _op("%constant_dynamic-slice_fusion.8 = bf16[1,5120,2,17408] fusion(%a, "
        "%b), kind=kLoop, calls=%f", 10, 8,
        "jit(tick)/decode-fwd/while/body/dynamic_slice"),
    _op("%slice_bitcast_fusion.2 = bf16[18432,4096] fusion(%a), kind=kLoop",
        18, 2, "jit(tick)/decode-fwd/while/body/attention/window/dot_general"),
    _op("%fusion.699.remat = bf16[1,4096,2,16384] fusion(%a), kind=kLoop",
        20, 4, "jit(tick)/decode-fwd/while/body/closed_call/slice"),
    _op("%fusion.12 = bf16[4,64] fusion(%a), kind=kLoop", 24, 1,
        "jit(tick)/decode-fwd/moe/shared_expert/mlp/reshape;squeeze"),
]
COMPUTING = [   # 75 ms, none of it counted
    _op("%fusion.192 = bf16[40,1,2,17408] fusion(%copy.108, %x), "
        "kind=kOutput, calls=%conv", 25, 30,
        "jit(tick)/decode-fwd/while/body/mlp/dot_general"),
    _op("%fusion.5 = bf16[40,17408] fusion(%a), kind=kOutput", 55, 5,
        "jit(tick)/decode-fwd/while/body/closed_call/slice"),
    _op("%bitcast_dynamic-update-slice_fusion.3 = f32[4,41] fusion(%a), "
        "kind=kLoop", 60, 5, "jit(tick)/decode-fwd/dynamic_update_slice"),
    _op("%bitcast_add_fusion.1 = f32[40] fusion(%a), kind=kLoop", 65, 5,
        "jit(tick)/add"),
    _op('%retention_sweep.1 = f32[40] custom-call(%a), '
        'custom_call_target="tpu_custom_call"', 70, 20,
        "jit(tick)/decode-fwd/retention/slice"),
    _op("%copy-start.4 = (bf16[8], bf16[8]) copy-start(%a)", 90, 5, ""),
    _op("%scatter.2 = bf16[8,128] scatter(%a, %b, %c)", 95, 5,
        "jit(tick)/scatter"),
]
# a copy of another program (the harness's own): busy, but no tick's
OUTSIDE = [_op("%copy.3 = f32[128] copy(%x)", 100, 25, "jit(other)/copy")]


def test_counts_the_moving_operations_of_the_tick_alone():
    run = _run(MOVING + COMPUTING + OUTSIDE,
               [TICK, ("jit_other(9)", 100.0, 125.0)])
    assert abs(READER.reduce(run) - 100.0 * 25 / 125) < 1e-9


def test_each_name_by_itself():
    assert all(READER.moves(op) for op in MOVING)
    assert not any(READER.moves(op) for op in COMPUTING)


def test_zero_where_a_tick_moved_nothing_and_nothing_without_a_tick():
    assert READER.reduce(_run(COMPUTING, [TICK])) == 0.0
    assert READER.reduce(_run(OUTSIDE, [("jit_other(9)", 100.0, 125.0)])) is None
    assert READER.reduce(types.SimpleNamespace(trace=None)) is None
