"""The readers of the patterned, expert-sharded train cell on a hand-made
capture, and ``lib/flops_hybrid.py`` against a hand count for one period at
a small size.

The capture (microseconds from the lines' timestamp): the train step runs
three times, 0-100, 100-200 and 200-260 (the last cut by the capture's end,
so two whole steps).  In each whole step: a flash forward in the global
layer 0-20, in a window layer 20-30, the backward's two kernels in a window
layer 30-40 and 40-50, a grouped-matmul kernel under `moe/expert_gemm`
50-70, a router fusion under `moe/router` 70-74, the optimizer 74-84; idle
84-100.  The host plane holds one `train-moe` span a step.
"""

import types
import warnings

import pytest

from benchmark.lib import (
    cells,
    flops,
    flops_hybrid,
    peaks,
    spans,
    trace,
    train_spans,
)
from benchmark.selftest.test_spans import LAYER_METRICS

US = 10 ** 6     # picoseconds


def _ev(mid, start_us, dur_us, stats=""):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} {stats} }}")


def _step(t0):
    return " ".join([
        _ev(2, t0 + 0, 20), _ev(3, t0 + 20, 10), _ev(4, t0 + 30, 10),
        _ev(5, t0 + 40, 10), _ev(6, t0 + 50, 20), _ev(7, t0 + 70, 4),
        _ev(8, t0 + 74, 10)])


def _moe(start_us, step, made, held):
    return _ev(1, start_us, 0, f"stats {{ metadata_id: 1 int64_value: {step} }} "
               f"stats {{ metadata_id: 2 int64_value: {made} }} "
               f"stats {{ metadata_id: 3 int64_value: {held} }} "
               f"stats {{ metadata_id: 4 int64_value: 0 }}")


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s %s %s }
  event_metadata { key: 1 value { id: 1 name: "jit_train_step(99)" } }
  event_metadata { key: 2 value { id: 2 name: "%%flash_fwd.1 = bf16[8] %s" } }
  event_metadata { key: 3 value { id: 3 name: "%%flash_fwd.2 = bf16[8] %s" } }
  event_metadata { key: 4 value { id: 4 name: "%%flash_bwd_dq.1 = bf16[8] %s" } }
  event_metadata { key: 5 value { id: 5 name: "%%flash_bwd_dkv.1 = bf16[8] %s" } }
  event_metadata { key: 6 value { id: 6 name: "%%gmm.1 = bf16[8] %s" } }
  event_metadata { key: 7 value { id: 7 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kLoop" } }
  event_metadata { key: 8 value { id: 8 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000 %s %s }
  event_metadata { key: 1 value { id: 1 name: "train-moe" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "assignments" } }
  stat_metadata { key: 3 value { id: 3 name: "held" } }
  stat_metadata { key: 4 value { id: 4 name: "dropped" } }
}
""" % (_ev(1, 0, 100), _ev(1, 100, 100), _ev(1, 200, 60),
       _step(0), _step(100), _ev(2, 200, 20),
       PALLAS, PALLAS, PALLAS, PALLAS, PALLAS,
       _moe(50, 11, 1000, 240), _moe(150, 12, 1000, 260))

FWD = "jit(train_step)/while/body/checkpoint/"
BWD = "jit(train_step)/transpose(jvp())/"
OP_NAMES = {
    "flash_fwd.1": FWD + "attention/global/pallas_call",
    "flash_fwd.2": FWD + "rematted_computation/attention/window/pallas_call",
    "flash_bwd_dq.1": BWD + "attention/window/pallas_call",
    "flash_bwd_dkv.1": BWD + "attention/window/pallas_call",
    "gmm.1": FWD + "moe/expert_gemm/pallas_call",
    "fusion.1": FWD + "moe/router/dot_general",
    "fusion.2": "jit(train_step)/optimizer/add",
}

# one period at a small size, under the published names
MODEL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=4,
             moe_num_active_primary_experts=3, router_width=16,
             sliding_window_size=8, sliding_window_layout=[0, 1, 1, 1],
             rope_layout=[0, 1, 1, 1], num_hidden_layers=4, vocab_size=100)


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def run():
    profile = _profile(CAPTURE)
    reduced = trace.reduce_profile(profile, OP_NAMES)
    found = spans.from_profile(profile, {"train-moe"})
    cell = types.SimpleNamespace(model=MODEL, traffic={"seq_length": 32})
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, tokens_per_step=32, _train_moe_spans=found)


def _reader(name):
    return cells.Cell.reader_at(LAYER_METRICS + "/" + name + ".py")


def test_two_whole_steps_and_their_kernels(run):
    steps = run.trace.full_runs(r"^jit_train_step\(")
    assert [(s, e) for s, e in steps] == [(1000, 101000), (101000, 201000)]
    # the grouped-matmul kernel is a Pallas call and not a flash kernel
    flash = run.trace.self_seconds_within(train_spans.is_flash, steps)
    every = run.trace.self_seconds_within(lambda o: o.is_pallas, steps)
    assert flash == pytest.approx(100e-6) and every == pytest.approx(140e-6)
    assert [s.args["held"] for s in train_spans.moe_spans(run)] == [240, 260]


def test_window_attn_share(run):
    # window kernels 10 + 10 + 10 of 50 us of flash kernels a step
    assert _reader("window_attn_share.smallthinker").reduce(run) == \
        pytest.approx(60.0)


def test_moe_share(run):
    # (20 + 4) of 84 busy us a whole step; the cut step adds 20 busy us
    assert _reader("moe_share.smallthinker").reduce(run) == pytest.approx(
        100.0 * 48 / (2 * 84 + 20))


def test_held_assignment_share(run):
    assert _reader("held_assignment_share.smallthinker").reduce(run) == \
        pytest.approx(25.0)


def test_flash_roofline_counts_each_layers_own_mask(run):
    cost = flops_hybrid.flash_train_cost(MODEL, 32, 2)
    # seq 32: the global layer sees 32 * 33 / 2 = 528 key-query pairs, a
    # window layer 8 * 9 / 2 + 24 * 8 = 228; 4 * n * d = 256 FLOPs a pair
    # forward, three times that with the backward; two sequences
    assert cost["flops"] == 2 * 3 * 256 * (528 + 3 * 228)
    assert cost["window_flops"] == 2 * 3 * 256 * 3 * 228
    # bytes: q and o [32, 4, 16], k and v [32, 2, 16] bf16: 2 q + 2 kv
    # forward, 4 + 4 backward, four layers, two sequences
    assert cost["bytes"] == 2 * 4 * (6 * 32 * 64 * 2 + 6 * 32 * 32 * 2)
    least, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                          run.peaks)
    got = _reader("flash_attn_roofline.smallthinker").reduce(run)
    assert got == pytest.approx(100.0 * least / 100e-6)
    assert 0 < got < 100


def test_expert_gemm_roofline_counts_the_held_rows(run):
    # 250 held rows a step (the spans' mean), two whole steps
    cost = flops_hybrid.expert_gemm_train_cost(MODEL, 500, 2)
    per_expert = 3 * 64 * 32
    assert cost["flops"] == 3 * 2 * 500 * per_expert
    assert cost["bytes"] == 3 * (2 * 4 * 4 * per_expert + 2 * 500 * 64) * 2
    least, _ = flops.roofline_seconds(cost["flops"], cost["bytes"], run.peaks)
    got = _reader("expert_gemm_roofline.smallthinker").reduce(run)
    assert got == pytest.approx(100.0 * least / 40e-6)
    assert 0 < got < 100


def test_train_flops_per_token_by_hand():
    attn = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64         # qkv + output
    dense = 4 * (attn + 64 * 16) + 64 * 100             # + router, + head
    assert flops_hybrid.dense_matmul_params(MODEL) == dense
    held = 3 * 4 / 16                                    # 0.75 a token, layer
    keys = (528 + 3 * 228) / 32                          # a token, all layers
    fwd = 2 * dense + 2 * 4 * held * 3 * 64 * 32 + 256 * keys
    assert flops_hybrid.train_flops_per_token(MODEL, 32) == pytest.approx(
        3 * fwd)
    assert flops_hybrid.layer_windows(MODEL) == [None, 8, 8, 8]


def test_train_mfu_reads_the_rate(run):
    run2 = types.SimpleNamespace(**vars(run))
    run2.first_window_draw, run2.last_window_draw = 10, 20
    run2.t_open, run2.t_close = 0.0, 2.0
    rate = 10 * 32 / 2.0
    want = 100 * flops_hybrid.train_flops_per_token(MODEL, 32) * rate / 197e12
    assert _reader("train_mfu.smallthinker").reduce(run2) == pytest.approx(want)


def test_readers_report_nothing_without_their_source(run):
    """A parent commit's program has no `train-moe` span and no
    `attention/window` scope: the readers return None and do not raise."""
    bare = types.SimpleNamespace(**vars(run))
    bare._train_moe_spans = []
    assert _reader("held_assignment_share.smallthinker").reduce(bare) is None
    assert _reader("expert_gemm_roofline.smallthinker").reduce(bare) is None
    unscoped = types.SimpleNamespace(**vars(run))
    unscoped.trace = trace.reduce_profile(_profile(CAPTURE), {})
    assert _reader("window_attn_share.smallthinker").reduce(unscoped) is None
    assert _reader("moe_share.smallthinker").reduce(unscoped) is None
    none = types.SimpleNamespace(trace=None, peaks=None, cell=run.cell,
                                 chips=1, tokens_per_step=32,
                                 first_window_draw=None, last_window_draw=None)
    for name in ("train_mfu", "flash_attn_roofline", "window_attn_share",
                 "moe_share", "expert_gemm_roofline", "held_assignment_share"):
        assert _reader(name + ".smallthinker").reduce(none) is None
