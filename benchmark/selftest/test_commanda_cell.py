"""The Command A+ cell's own pieces: ``lib/flops_commanda.py`` against hand
counts either side of the window, its readers on counters made by hand and
on a hand-made capture, the parent's counters and a capture without the
scopes (nothing is reported, nothing raises), and a CPU rehearsal of the
cell that primes its prefixes, hits them and compares correct.

The capture (microseconds from the lines' timestamp): the tick program runs
four times, 0-60 (launched before the capture opened), 100-200 and 220-320
(whole: ticks 8 and 9) and 340-400 (its fetch lies past the capture's end).
In each whole tick: three `paged_attention` kernels under `attention/window`
of 10 us each, one under `attention/global` of 20, a grouped-matmul kernel
under `moe/expert_gemm` of 30, a router fusion of 5.  The host plane holds
the scheduler's spans and one `engine-moe` a tick."""

import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_commanda, peaks, spans, trace
from benchmark.selftest.test_spans import LAYER_METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "commanda_plus_agent_16k"
US = 10 ** 6     # picoseconds
KEY = 2 * 8 * 128 * 2        # a key and its value in one layer: 4,096 bytes


def _reader(name):
    return cells.Cell.reader_at(os.path.join(LAYER_METRICS, name + ".py"))


def _model():
    return cells.Cell(CELL).model


# ---- bytes and operations by hand ------------------------------------------

def test_the_configuration_is_the_catalog_rows_but_for_its_cuts():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    body = cells.Cell(CELL).config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if body.get(k, "absent") != v]
    assert sorted(differs) == sorted(body["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    f = body["flags"]
    assert f["moe_experts_held"] == body["num_experts"] == 16
    assert f["num_layers"] == body["num_hidden_layers"] == 4


@pytest.mark.parametrize("context, window_keys", [(1000, 1000), (16900, 4096)])
def test_visible_key_bytes_either_side_of_the_window(context, window_keys):
    model = _model()
    assert flops_commanda.key_bytes(model) == KEY
    assert flops_commanda.layer_windows(model) == [4096, 4096, 4096, None]
    need = flops_commanda.visible_key_bytes(model, context)
    assert need == {"window": 3 * window_keys * KEY, "full": context * KEY}
    # the issue's arithmetic: 119 MB a sequence a tick at 16.9k tokens where
    # four full layers would read 277, 42% of it by the window layers
    if context == 16900:
        total = need["window"] + need["full"]
        assert round(total / 1e6) == 120 and round(4 * context * KEY / 1e6) == 277
        assert round(100 * need["window"] / total) == 42


def test_held_gemm_cost_by_hand():
    model = _model()
    assert flops_commanda.expert_params(model) == 3 * 4096 * 4096 == 50331648
    cost = flops_commanda.held_gemm_cost(model, 256.0, 60.0)
    assert cost["flops"] == 2.0 * 256 * 50331648
    assert cost["bytes"] == (60 * 50331648 + 2 * 256 * 4096) * 2


def test_needed_bytes_counts_a_prompt_from_its_cached_prefix_on():
    need = _reader("paged_attn_roofline.commanda").needed_bytes
    model = _model()
    per = lambda c: 3 * min(c, 4096) * KEY + c * KEY        # noqa: E731
    # one token received in the span, at a context of 16,500 + 3
    decode = {"n_prompt": 16500, "token_t": [0.5, 5.0, 5.1, 5.2], "sent_t": 0.1,
              "prefix": 2}
    total, window = need(model, [decode], (5.15, 6.0), 64, 16, 16384)
    assert total == per(16503) and window == 3 * 4096 * KEY
    # a prompt of 16,384 + 100 prefilled wholly inside the span: the cache
    # served 16,368 tokens, so two chunks: to 16,432 and to the end
    fresh = {"n_prompt": 16484, "token_t": [3.0], "sent_t": 2.0, "prefix": 0}
    total, _ = need(model, [fresh], (1.0, 4.0), 64, 16, 16384)
    assert total == per(16484) + per(16432) + per(16484)    # + its first token
    # without a prefix every chunk from position 0 counts
    alone = {"n_prompt": 100, "token_t": [3.0], "sent_t": 2.0, "prefix": None}
    total, _ = need(model, [alone], (1.0, 2.5), 64, 16, 16384)
    assert total == 0.5 * (per(64) + per(100))              # half its prefill


# ---- counters -----------------------------------------------------------------

PARENT = {"mlt_engine_ticks_total": 2000.0,
          "mlt_engine_moe_assignments_total": 4.0e6,
          "mlt_engine_moe_experts_touched_total": 9.0e5}
CHANGE = {**PARENT,
          "mlt_engine_seq_ticks_total": 100000.0,
          'mlt_engine_seq_pages_sum{class="window"}': 25.8e6,
          'mlt_engine_seq_pages_sum{class="full"}': 109.0e6,
          "mlt_engine_moe_held_assignments_total": 5.0e5,
          "mlt_engine_moe_held_experts_touched_total": 1.25e5}


def _counted(counters):
    return types.SimpleNamespace(counters=dict(counters), trace=None)


def test_counter_readers_return_the_stated_quotients():
    run = _counted(CHANGE)
    assert _reader("window_pages_per_seq.commanda").reduce(run) == pytest.approx(258.0)
    assert _reader("rows_per_expert.commanda").reduce(run) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["window_pages_per_seq.commanda",
                                  "rows_per_expert.commanda"])
def test_counter_readers_leave_the_metric_out_on_the_parent(name):
    assert _reader(name).reduce(_counted(PARENT)) is None
    assert _reader(name).reduce(_counted({})) is None


# ---- the capture ----------------------------------------------------------------

def _ev(mid, start_us, dur_us, stats=""):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} {stats} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 10), _ev(2, t0 + 10, 10), _ev(2, t0 + 20, 10),
                     _ev(3, t0 + 30, 20), _ev(4, t0 + 50, 30),
                     _ev(5, t0 + 80, 5)])


def _stat(mid, value):
    return f"stats {{ metadata_id: {mid} int64_value: {value} }}"


def _step(t0, tick, moe):
    """One scheduler step: launch ``tick`` at t0, then fetch the tick before
    and note what its router did."""
    out = [_ev(1, t0, 95, _stat(1, tick)),
           _ev(2, t0 + 2, 6, _stat(1, tick) + " " + _stat(2, 0)),
           _ev(3, t0 + 10, 70)]
    if moe:
        out.append(_ev(4, t0 + 81, 0, " ".join(
            _stat(m, v) for m, v in zip((1, 3, 4, 5, 6), moe))))
    return " ".join(out)


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s %s %s %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s %s %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%paged_attention.1 = bf16[8] %s" } }
  event_metadata { key: 3 value { id: 3 name: "%%paged_attention.2 = bf16[8] %s" } }
  event_metadata { key: 4 value { id: 4 name: "%%gmm.1 = bf16[8] %s" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kLoop" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000 %s %s %s %s }
  event_metadata { key: 1 value { id: 1 name: "engine-step" } }
  event_metadata { key: 2 value { id: 2 name: "engine-launch" } }
  event_metadata { key: 3 value { id: 3 name: "engine-fetch" } }
  event_metadata { key: 4 value { id: 4 name: "engine-moe" } }
  stat_metadata { key: 1 value { id: 1 name: "tick" } }
  stat_metadata { key: 2 value { id: 2 name: "prefill_rows" } }
  stat_metadata { key: 3 value { id: 3 name: "assignments" } }
  stat_metadata { key: 4 value { id: 4 name: "touched" } }
  stat_metadata { key: 5 value { id: 5 name: "held" } }
  stat_metadata { key: 6 value { id: 6 name: "held_touched" } }
}
""" % (_ev(1, 0, 60), _ev(1, 100, 100), _ev(1, 220, 100), _ev(1, 340, 60),
       _ev(5, 0, 60), _tick_ops(100), _tick_ops(220),
       PALLAS, PALLAS, PALLAS,
       # tick 8 is launched at 95, tick 9 at 215, tick 10 at 335; the step
       # that launches tick n+1 fetches tick n and notes its router
       _step(95, 8, None), _step(215, 9, (8, 2048, 400, 250, 60)),
       _step(335, 10, (9, 2048, 410, 262, 62)),
       _ev(3, 432, 2))

FWD = "jit(tick)/ragged-fwd/while/body/closed_call/checkpoint/"
OP_NAMES = {
    "paged_attention.1": FWD + "attention/window/pallas_call",
    "paged_attention.2": FWD + "attention/global/pallas_call",
    "gmm.1": FWD + "moe/expert_gemm/pallas_call",
    "fusion.1": FWD + "moe/router/dot_general",
}


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


@pytest.fixture()
def run(monkeypatch):
    from jax.profiler import ProfileData

    profile = _profile(CAPTURE)
    monkeypatch.setattr(ProfileData, "from_file",
                        staticmethod(lambda path: profile))
    reduced = trace.reduce_profile(profile, OP_NAMES)
    reduced.path = "the capture above"
    cell = types.SimpleNamespace(model=_model(), traffic={})
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, counters={})


def test_window_attn_share_reads_the_scopes(run):
    # 2 whole ticks x (3 x 10 us under window, 20 us under global)
    assert _reader("window_attn_share.commanda").reduce(run) == pytest.approx(60.0)


def test_expert_gemm_roofline_counts_the_held_rows_of_the_timed_ticks(run, capsys):
    got = _reader("expert_gemm_roofline.commanda").reduce(run)
    # ticks 8 and 9 are whole and noted: 250 + 262 held rows on 60 + 62 held
    # experts; 60 us under moe/expert_gemm
    cost = flops_commanda.held_gemm_cost(_model(), 512.0, 122.0)
    least = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert got == pytest.approx(100.0 * least / 60e-6)
    assert "512 held rows on 122 held experts" in capsys.readouterr().out
    # the parent's spans carry no `held`: nothing is reported
    found = spans.from_profile(_profile(CAPTURE), spans.SPAN_NAMES | {"engine-moe"})
    assert sum(s.name == "engine-moe" and "held" in s.args for s in found) == 2


def test_readers_report_nothing_without_their_source(run):
    bare = types.SimpleNamespace(trace=None, peaks=run.peaks, cell=run.cell,
                                 counters={}, trace_host=None, all_samples=[],
                                 engine={})
    for name in ("paged_attn_roofline.commanda", "window_attn_share.commanda",
                 "expert_gemm_roofline.commanda"):
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scopes (the parent's)
    plain = trace.reduce_profile(_profile(CAPTURE), {})
    plain.path = ""
    unscoped = types.SimpleNamespace(trace=plain, peaks=run.peaks, cell=run.cell,
                                     counters={})
    assert _reader("window_attn_share.commanda").reduce(unscoped) is None
    assert _reader("expert_gemm_roofline.commanda").reduce(unscoped) is None
    # another configuration's model has no layer_types: not this reader's
    other = types.SimpleNamespace(
        trace=run.trace, peaks=run.peaks, trace_host=(0.0, 1.0), all_samples=[],
        engine={}, cell=types.SimpleNamespace(model={"hidden_size": 8}, traffic={}))
    assert _reader("paged_attn_roofline.commanda").reduce(other) is None


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_primed_hit_and_correct(capsys):
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths, a
    window of 32, probes of 72 and 88 tokens (2+ windows), three primed
    prefixes of 128 tokens: the pool's two classes serve them, the reference
    agrees at the emitted positions, and the counters the readers want are
    on /metrics."""
    from benchmark.lib import harness, serving

    cell = cells.Cell(CELL)
    args = types.SimpleNamespace(seed=2147485017, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["primed_prefixes"] == 3 and c["primed_tokens"] == 384
    assert c["prefixes_hit"] and c["prefix_hit_share"] >= 0.8
    assert c["probe_prefix_hit_tokens"] > 0
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 1e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    pages = _reader("window_pages_per_seq.commanda").reduce(run)
    assert 3 <= pages <= 32 // 8 + 2                   # its window, in pages
    assert _reader("rows_per_expert.commanda").reduce(run) > 0
    assert run.counters["mlt_engine_window_pages_released_total"] > 0
    assert 'mlt_engine_pool_pages{class="window",state="free"}' in run.counters
