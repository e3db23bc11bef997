"""The Brumby cell's own pieces: ``lib/flops_retention.py`` against a hand
count, its three readers on counters made by hand and on a hand-made
capture, the parent's counters and a capture without the scope or the
kernel (nothing is reported, nothing raises), and a CPU rehearsal of the
cell that serves its probes from the state pool and compares correct.

The capture (microseconds from the lines' timestamp): the tick program runs
twice, 100-200 and 220-320.  In each tick: one `retention_sweep` kernel of
40 us under `attention/retention`, a fusion of 10 us under the same scope
(the feature map), and an MLP fusion of 30 us outside it."""

import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_retention, peaks, trace
from benchmark.selftest.test_spans import LAYER_METRICS

CELL = "brumby14b_longgen_closed"
US = 10 ** 6     # picoseconds


def _reader(name):
    return cells.Cell.reader_at(os.path.join(LAYER_METRICS, name + ".py"))


def _model():
    return cells.Cell(CELL).model


# ---- the configuration and the bytes by hand -------------------------------

def test_the_configuration_is_the_catalog_row_but_for_its_depth():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    body = cells.Cell(CELL).config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if body.get(k, "absent") != v]
    assert differs == body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 40}
    f = body["flags"]
    assert f["num_layers"] == body["num_hidden_layers"] == 4
    assert f["vocab_size"] == body["vocab_size"] == 151936   # never sliced
    assert "kv_pool_pages" not in f and "prefix_cache" not in f
    assert body["assumed"]["degree"] == 2
    assert body["derived"]["state_dtype"] == "float32"
    for key in ("gate", "normaliser", "qk_norm", "rope", "state_dtype",
                "feature_layout", "gate_initialiser", "why"):
        assert key in body["assumed"], key
    mix = cells.Cell(CELL).traffic
    assert mix["clients"] == 2 * f["max_batch_slots"] == 80
    assert mix["probe_lengths"] == [1536, 2048] and mix["shared_prefix"] is None


def test_state_bytes_by_hand():
    model = _model()
    # the symmetric square of a 128-vector: 128 * 129 / 2 products
    assert flops_retention.minimal_features(model) == 8256
    # 8 KV heads x (8,256 x 128 state + 8,256 normaliser) x 4 bytes
    assert flops_retention.state_bytes(model) == 8 * (1056768 + 8256) * 4 \
        == 34080768 == model["state_bytes_minimal_per_layer_and_sequence"]
    # the program's tiled layout stores 8,704 rows for the 8,256 counted
    assert model["state_bytes_per_layer_and_sequence"] == 8 * (
        128 * 8704 + 8704) * 4
    assert flops_retention.row_bytes(model) == (40 + 16) * 128 * 4
    # a decode tick of 40 rows, 4 layers: each row a run of its own
    need = flops_retention.sweep_bytes(model, 40, 40)
    assert need == 4 * (2 * 40 * 34080768 + 40 * 28672)
    assert round(need / 1e9, 2) == 10.91      # the issue's "10.8 GB a tick"
    # 39 decode rows and one 64-row prompt run: 40 runs, 103 rows
    assert flops_retention.sweep_bytes(model, 40, 103) - need == \
        4 * 63 * 28672


# ---- counters ----------------------------------------------------------------

PARENT = {"mlt_engine_ticks_total": 2000.0,
          "mlt_engine_paged_rows_total": 9.0e4}
CHANGE = {"mlt_engine_ticks_total": 2000.0,
          "mlt_engine_state_rows_total": 103000.0,
          "mlt_engine_state_touches_total": 40000.0}


def _counted(counters):
    return types.SimpleNamespace(counters=dict(counters), trace=None)


def test_rows_per_touch_is_the_stated_quotient():
    reader = _reader("state_rows_per_touch.brumby")
    assert reader.reduce(_counted(CHANGE)) == pytest.approx(2.575)
    assert reader.reduce(_counted(PARENT)) is None      # the parent: nothing
    assert reader.reduce(_counted({})) is None


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 10), _ev(3, t0 + 10, 40), _ev(4, t0 + 50, 30)])


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%%retention_sweep.1 = f32[8] %s" } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
}
""" % (_ev(1, 100, 100), _ev(1, 220, 100), _tick_ops(100), _tick_ops(220),
       PALLAS)

FWD = "jit(tick)/decode-fwd/while/body/closed_call/"
OP_NAMES = {
    "fusion.1": FWD + "attention/retention/dot_general",
    "retention_sweep.1": FWD + "attention/retention/pallas_call",
    "fusion.2": FWD + "mlp/dot_general",
}


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


def _run(op_names, samples=(), span=(0.0, 1.0)):
    reduced = trace.reduce_profile(_profile(CAPTURE), op_names)
    reduced.path = "the capture above"
    cell = types.SimpleNamespace(model=_model(), traffic={})
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, counters={}, trace_host=span, all_samples=list(samples),
        engine={"prefill_chunk": 64})


def test_retention_share_reads_the_scope():
    # 2 ticks x (10 + 40 us under retention) of 2 x 80 us busy
    assert _reader("retention_share.brumby").reduce(_run(OP_NAMES)) == \
        pytest.approx(62.5)


def test_retention_roofline_counts_runs_and_rows(capsys):
    model = _model()
    # 3 tokens received in the span: three decode rows, a run each; and a
    # prompt of 130 tokens prefilled half inside it: 129 rows in 3 ticks
    decode = {"n_prompt": 300, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
              "sent_t": -9.0}
    fresh = {"n_prompt": 130, "token_t": [2.0], "sent_t": -1.0}
    got = _reader("retention_roofline.brumby").reduce(
        _run(OP_NAMES, [decode, fresh], (0.0, 1.0)))
    # the prompt's prefill ran from -1.0 to 2.0: a third of it in the span
    need = flops_retention.sweep_bytes(model, 3 + 3 / 3, 3 + 129 / 3)
    assert got == pytest.approx(100.0 * need / 819e9 / 80e-6)
    assert "4 runs of 46 rows" in capsys.readouterr().out


def test_readers_report_nothing_without_their_source():
    bare = types.SimpleNamespace(trace=None, peaks=None, counters={},
                                 trace_host=None, all_samples=[], engine={},
                                 cell=types.SimpleNamespace(model=_model()))
    for name in ("retention_share.brumby", "retention_roofline.brumby",
                 "state_rows_per_touch.brumby"):
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scope or the kernel (the parent's)
    plain = _run({})
    assert _reader("retention_share.brumby").reduce(plain) is None
    unnamed = CAPTURE.replace("retention_sweep", "paged_attention")
    reduced = trace.reduce_profile(_profile(unnamed), {})
    reduced.path = ""
    plain.trace = reduced
    assert _reader("retention_roofline.brumby").reduce(plain) is None


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_correct_on_the_state_pool(capsys):
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths
    (8 / 2 heads of 16, 192 features), four slots, probes of 24 and 40
    tokens: the state pool serves them (no prefix cache, so the probes need
    hit nothing), the reference agrees at the emitted positions, and the
    counters the readers want are on /metrics."""
    from benchmark.lib import harness, serving

    cell = cells.Cell(CELL)
    args = types.SimpleNamespace(seed=2147485017, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] is None and not c["prefix_hit_tokens"]
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 1e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    per_touch = _reader("state_rows_per_touch.brumby").reduce(run)
    assert per_touch > 1.0                  # prompt runs share their touch
    assert run.counters["mlt_engine_state_resets_total"] > 0
    assert "mlt_engine_state_recomputed_tokens_total" in run.counters
    assert "mlt_engine_state_pool_bytes" in run.counters
