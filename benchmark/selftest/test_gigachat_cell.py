"""The GigaChat3.5 cell's own pieces: its configuration against the catalog
row, ``lib/flops_delta.py`` against a hand count, its readers on counters
made by hand and on a hand-made capture, the parent's counters and a capture
without the scopes or the kernel (nothing is reported, nothing raises), and
a CPU rehearsal of the cell that serves its probes from BOTH pools and
compares correct.

The capture (microseconds from the lines' timestamp): the tick program runs
twice, 100-200 and 220-320.  In each tick: one `delta_sweep` kernel of 30 us
and a fusion of 10 us under `attention/delta`, one `paged_attention` kernel
of 10 us under `attention/mla`, and an expert fusion of 30 us under `moe`."""

import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_delta, peaks, trace
from benchmark.selftest.test_spans import LAYER_METRICS

CELL = "gigachat35_reasoning_closed"
US = 10 ** 6     # picoseconds
READERS = ("delta_share.gigachat", "delta_roofline.gigachat",
           "mla_attn_roofline.gigachat", "expert_gemm_roofline.gigachat",
           "rows_per_expert.gigachat")


def _reader(name):
    return cells.Cell.reader_at(os.path.join(LAYER_METRICS, name + ".py"))


def _model():
    return cells.Cell(CELL).model


# ---- the configuration and the bytes by hand -------------------------------

def test_the_configuration_is_the_catalog_row_but_for_its_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GigaChat3.5-432B-A28B")
    body = cells.Cell(CELL).config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if body.get(k, "absent") != v]
    assert sorted(differs) == sorted(body["reduced"])
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    # the floors: one whole period, four layers after the dense one, >= 8
    # experts held, an eighth of the vocabulary; no width among the cuts
    assert body["num_hidden_layers"] == 5 and body["first_k_dense_replace"] == 1
    assert body["full_attention_layers"] == [1]          # the model's layer 3
    assert body["n_routed_experts"] == 16 >= 8
    assert body["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert not [k for k in body["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    f = body["flags"]
    assert f["num_layers"] + f["dense_prefix_layers"] == 5
    assert f["moe_experts_held"] == body["n_routed_experts"]
    assert f["moe_capacity_factor"] == 256 / 16       # nothing is dropped
    assert f["vocab_size"] == body["vocab_size"]
    assert "kv_pool_pages" not in f and "prefix_cache" not in f
    d = body["derived"]
    assert d["router_width"] == 256 and d["state_dtype"] == "float32"
    assert d["linear_layers"] == 4 and d["latent_layers"] == 1
    for key in ("layernorm_type", "norm", "mla_scaling", "rope",
                "gated_attention", "linear_attention", "linear_gating_type",
                "state_dtype", "decay_initialiser", "router", "swiglu_limit",
                "mtp", "why"):
        assert key in body["assumed"], key
    mix = cells.Cell(CELL).traffic
    assert mix["clients"] == 2 * f["max_batch_slots"] == 256
    assert mix["probe_lengths"] == [1536, 2048] and mix["shared_prefix"] is None
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= f["engine_max_seq"]
    # the cell reports the new readers, each with this cell alone
    bench = cells.Cell(CELL).bench
    for name in READERS:
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL], name
        assert os.path.isfile(os.path.join(LAYER_METRICS, name + ".py"))


def test_bytes_by_hand():
    model = _model()
    assert flops_delta.linear_layers(model) == 4
    assert flops_delta.latent_layers(model) == 1
    # 64 value heads x [128, 128] float32
    assert flops_delta.state_bytes(model) == 64 * 128 * 128 * 4 == 4194304
    # with the conv's 3-row tail (3 x 16,384 float32): what a slot keeps
    assert model["state_bytes_per_layer_and_sequence"] == 4194304 + 3 * 16384 * 4
    # q and k of 32 key heads, v of 64 value heads, g and beta a value head
    assert flops_delta.row_bytes(model) == (2 * 32 * 128 + 64 * 128 + 128) * 4
    # a decode tick of 128 rows, 4 linear layers: each row a run of its own
    need = flops_delta.sweep_bytes(model, 128, 128)
    assert need == 4 * (2 * 128 * 4194304 + 128 * 66048)
    assert round(need / 1e9, 2) == 4.33       # the issue's "4.5 GB a tick"
    # 127 decode rows and one 128-row prompt run: 128 runs, 255 rows
    assert flops_delta.sweep_bytes(model, 128, 255) - need == 4 * 127 * 66048
    # ONE latent layer: 576 bf16 values a token (the pool stores 640 lanes)
    assert flops_delta.latent_bytes_per_token(model) == 576 * 2
    assert flops_delta.expert_params(model) == 3 * 7168 * 2048 \
        == model["expert_params"]
    cost = flops_delta.held_gemm_cost(model, 512.0, 64.0)
    assert cost["flops"] == 2 * 512 * 44040192
    assert cost["bytes"] == (64 * 44040192 + 2 * 512 * 7168) * 2


# ---- counters ----------------------------------------------------------------

def _counted(counters):
    return types.SimpleNamespace(counters=dict(counters), trace=None)


def test_rows_per_held_expert_is_the_stated_quotient():
    reader = _reader("rows_per_expert.gigachat")
    got = reader.reduce(_counted({
        "mlt_engine_moe_held_assignments_total": 512000.0,
        "mlt_engine_moe_held_experts_touched_total": 128000.0}))
    assert got == pytest.approx(4.0)
    assert reader.reduce(_counted({"mlt_engine_ticks_total": 9.0})) is None


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 10), _ev(3, t0 + 10, 30), _ev(4, t0 + 40, 10),
                     _ev(5, t0 + 50, 30)])


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%%delta_sweep.1 = f32[8] %s" } }
  event_metadata { key: 4 value { id: 4 name: "%%paged_attention.1 = f32[8] %s" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
}
""" % (_ev(1, 100, 100), _ev(1, 220, 100), _tick_ops(100), _tick_ops(220),
       PALLAS, PALLAS)

FWD = "jit(tick)/ragged-fwd/while/body/closed_call/"
OP_NAMES = {
    "fusion.1": FWD + "attention/delta/dot_general",
    "delta_sweep.1": FWD + "attention/delta/pallas_call",
    "paged_attention.1": FWD + "attention/mla/pallas_call",
    "fusion.2": FWD + "moe/expert_gemm/dot_general",
}


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


def _run(op_names, samples=(), span=(0.0, 1.0), capture=CAPTURE):
    reduced = trace.reduce_profile(_profile(capture), op_names)
    reduced.path = ""
    cell = types.SimpleNamespace(model=_model(), traffic={})
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, counters={}, trace_host=span, all_samples=list(samples),
        engine={"prefill_chunk": 64, "max_slots": 128})


def test_delta_share_reads_the_scope():
    # 2 ticks x (10 + 30 us under attention/delta) of 2 x 80 us busy
    assert _reader("delta_share.gigachat").reduce(_run(OP_NAMES)) == \
        pytest.approx(50.0)


# 3 tokens received in the span: three decode rows, a run each; and a
# prompt of 300 tokens prefilled a third inside it: 299 rows in 3 ticks of
# at most 128 rows (the engine's cap for 128 slots)
DECODE = {"n_prompt": 300, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
          "sent_t": -9.0}
FRESH = {"n_prompt": 300, "token_t": [2.0], "sent_t": -1.0}


def test_delta_roofline_counts_runs_and_rows(capsys):
    got = _reader("delta_roofline.gigachat").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    need = flops_delta.sweep_bytes(_model(), 3 + 3 / 3, 3 + 299 / 3)
    assert got == pytest.approx(100.0 * need / 819e9 / 60e-6)
    assert "4 runs of 103 rows" in capsys.readouterr().out


def test_mla_roofline_prices_one_latent_layer():
    got = _reader("mla_attn_roofline.gigachat").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    # contexts 301, 302, 303 of the decode rows; the prompt's chunks of 64
    # see 64, 128, 192, 256, 300 cached tokens, a third of them in the span
    keys = 301 + 302 + 303 + (64 + 128 + 192 + 256 + 300) / 3
    assert got == pytest.approx(100.0 * keys * 1152 / 819e9 / 20e-6)


def test_readers_report_nothing_without_their_source():
    bare = types.SimpleNamespace(trace=None, peaks=None, counters={},
                                 trace_host=None, all_samples=[], engine={},
                                 cell=types.SimpleNamespace(model=_model()))
    for name in READERS:
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scopes or the kernel (the
    # parent's), and a cell whose model has no linear layers
    plain = _run({})
    assert _reader("delta_share.gigachat").reduce(plain) is None
    unnamed = CAPTURE.replace("delta_sweep", "retention_sweep")
    assert _reader("delta_roofline.gigachat").reduce(
        _run({}, capture=unnamed)) is None
    assert _reader("expert_gemm_roofline.gigachat").reduce(plain) is None
    other = _run(OP_NAMES)
    other.cell = types.SimpleNamespace(model={"hidden_size": 64})
    assert _reader("delta_roofline.gigachat").reduce(other) is None
    assert _reader("mla_attn_roofline.gigachat").reduce(other) is None


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_correct_on_both_pools():
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths,
    four slots, probes of 24 and 40 tokens, 4 held experts of 16 from the
    fourth on: the latent pages and the state slots serve them (no prefix
    cache, so the probes need hit nothing), the reference agrees at the
    emitted positions, and the counters the readers want are on /metrics."""
    from benchmark.lib import harness, serving

    cell = cells.Cell(CELL)
    args = types.SimpleNamespace(seed=2147485019, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] is None and not c["prefix_hit_tokens"]
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 1e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    assert _reader("state_rows_per_touch.brumby").reduce(run) > 1.0
    assert _reader("paged_rows_per_walk.batch").reduce(run) >= 1.0
    assert _reader("rows_per_expert.gigachat").reduce(run) > 0
    assert run.counters["mlt_engine_state_resets_total"] > 0
    for name in ("mlt_engine_state_recomputed_tokens_total",
                 "mlt_engine_state_pool_bytes",
                 'mlt_engine_pool_pages{class="state",state="referenced"}',
                 'mlt_engine_pool_pages{class="full",state="referenced"}'):
        assert name in run.counters, name
