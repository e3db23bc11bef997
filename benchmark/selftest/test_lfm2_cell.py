"""The LFM2-24B-A2B cell's own pieces: its entries in the index (found
WHEREVER they stand: a later PR appends behind them), its configuration
against the catalog row, ``lib/flops_lfm2.py`` against a hand count, its
readers on a hand-made capture, the parent's counters and a capture without
the scopes or the kernel (nothing is reported, nothing raises), and a CPU
rehearsal of the cell that serves its probes from BOTH pools and compares
correct.

The capture (microseconds from the lines' timestamp): the tick program runs
three times, 100-200, 220-320 and 340-440.  In each tick: a projection
fusion of 30 us under `short_conv/in_proj` and a conv fusion of 10 us under
`short_conv/conv`, one `paged_attention` kernel of 10 us under
`attention/global`, and an expert fusion of 30 us under `moe`."""

import functools
import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_lfm2, peaks, trace

CELL = "lfm2_24b_chat_closed"
US = 10 ** 6     # picoseconds
READERS = ("conv_share.lfm2", "conv_roofline.lfm2",
           "expert_gemm_roofline.lfm2", "rows_per_expert.lfm2",
           "paged_attn_roofline.lfm2")
TYPES = ["conv", "conv", "full_attention", "conv"] * 10


@functools.cache
def _cell():
    return cells.Cell(CELL)


def _reader(name):
    return cells.Cell.reader_at(os.path.join(
        _cell().bench_dir, "layer_metrics", name + ".py"))


def _model():
    return _cell().model


def test_the_cells_entries_are_in_the_index_once_each():
    """One configuration, one cell, five readers with this cell alone, and
    the cell's name ONCE in each list it joined and behind every name that
    was there before it; wherever later PRs' entries stand."""
    index = _cell().bench
    assert len(json.dumps(index, indent=1)) < 64 * 1024
    assert [c["name"] for c in index["configs"]].count("lfm2-24b-a2b") == 1
    assert [w["name"] for w in index["workloads"]].count(CELL) == 1
    entry = _cell().entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2-24b-a2b", "chat_rag_closed_256", 1)
    config, = [c for c in index["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == ["num_experts"]
    for item in (config, entry):
        assert 1 <= len(item["why"]) <= 200
    names = [m["name"] for m in index["per_layer"]]
    for name in READERS:
        assert names.count(name) == 1, name
    accepted = ("falcon7b_batch_decode", "nemotron3_nano_chat_closed")
    for group in ("end_to_end", "per_layer"):
        for m in index[group]:
            named = m.get("workloads", [])
            assert named.count(CELL) <= 1, m["name"]
            if CELL in named:
                assert all(named.index(w) < named.index(CELL)
                           for w in accepted if w in named), m["name"]


# ---- the configuration and the bytes by hand -------------------------------

def test_the_configuration_is_the_catalog_row_but_for_its_share():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    cell = _cell()
    body = cell.config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if body.get(k, "absent") != v]
    assert differs == body["reduced"] == ["num_experts"]
    assert body["published"] == {"num_experts": row["config"]["num_experts"]}
    # NO depth cut and NO vocabulary cut: 40 layers in the published order,
    # 65,536 rows; 8 experts held, the floor; no width among the cuts
    assert body["num_hidden_layers"] == 40 == len(body["layer_types"])
    assert body["layer_types"] == TYPES and body["vocab_size"] == 65536
    assert body["num_experts"] == 8 and body["published"]["num_experts"] == 64
    f = body["flags"]
    assert f["model_name"] == body["preset"] == "lfm2-24b-a2b"
    assert f["moe_experts_held"] == body["num_experts"]
    assert f["moe_first_held_expert"] == 0
    assert f["moe_capacity_factor"] == 64 / 8          # nothing is dropped
    assert not {"num_layers", "sublayer_pattern", "vocab_size", "hidden_size",
                "ffn_hidden_size", "moe_ffn_hidden_size", "num_experts",
                "kv_channels"} & set(f)
    assert sorted(set(f) - {"model_name", "params_dtype", "tokenizer_type"}) \
        == sorted(body["changed_from_preset"])
    d = body["derived"]
    assert d["router_width"] == 64 and d["tail_dtype"] == "bfloat16"
    assert (d["conv_layers"], d["attention_layers"], d["dense_layers"],
            d["expert_layers"]) == (30, 10, 2, 38)
    assert d["expert_params"] == 3 * 2048 * 1536
    assert d["sublayer_pattern"] == "".join(
        ("C" if t == "conv" else "*") + ("D" if i < 2 else "E")
        for i, t in enumerate(TYPES))
    for key in ("layer", "tied_head", "short_conv", "tail_dtype", "attention",
                "router", "residual", "initialiser", "why"):
        assert key in body["assumed"], key
    for key in ("deployment", "reduced_why", "flags_why"):
        assert len(body[key]) > 200, key
    assert "8 chips that share each layer" in body["deployment"]
    tol = body["tolerance"]
    assert "why" in tol and 0 < tol["mean_abs_nats"] < tol["max_abs_nats"]
    # the traffic file holds exactly ISSUE 57's parameters
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["ramp_s"], mix["plan_requests"],
            mix["shared_prefix"]) == ("closed_loop", 512, 30, 4096, None)
    assert mix["clients"] == 2 * f["max_batch_slots"] == 512
    assert mix["draw_seed"] != 20261001                  # of its own
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.9, "min": 32, "max": 4096}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["sampling"] == {"top_k": 1,
                               "use_eod_token_for_early_termination": False}
    assert mix["probe_lengths"] == [600, 840]
    assert mix["trace_seconds"] in (1, 2, 3) and "trace_why" in mix
    assert all(n % f["prefill_chunk"] for n in mix["probe_lengths"])
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] + 32 \
        <= f["engine_max_seq"] == 5152
    # the probes warm every compiled tick shape: no prompt rows, and the cap
    assert f["max_batch_slots"] % f["prefill_chunk"] == 0
    assert f["max_batch_slots"] // f["prefill_chunk"] == 1
    # the cell reports the new readers, each with this cell alone
    for name in READERS:
        entry, = [m for m in cell.bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL], name
        assert os.path.isfile(os.path.join(
            cell.bench_dir, "layer_metrics", name + ".py"))
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
    reported = {m["name"] for m in cell.per_layer}
    assert {"tick_ms.batch", "moe_share.joyai", "state_rows_per_touch.brumby",
            "copy_share.batch", "paged_rows_per_walk.batch",
            "paged_fetch_share.batch", "pool_dry_tick_share.batch",
            "host_work_ms.batch", "setup_trace_lower_s"} <= reported
    # `paged_attn_roofline.batch` multiplies K/V by EVERY layer
    assert "paged_attn_roofline.batch" not in reported
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tokens_per_s", "setup_s"}


def test_the_preset_is_the_configuration_and_the_bytes_are_the_trees():
    """The program's preset carries every published size of the file, and
    ``reduced_why``'s bytes are the tree's own (``jax.eval_shape`` of the
    initialiser at the cell's flags)."""
    import jax

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.models import init_model_params

    cell = _cell()
    body = cell.config
    cfg = parse_args(cell.flags({"seed": 1}))
    m = cfg.model
    assert m.sublayer_pattern == body["derived"]["sublayer_pattern"]
    assert (m.hidden_size, m.ffn_hidden_size, m.moe_ffn_hidden_size,
            m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels,
            m.num_experts, m.moe_router_topk, m.vocab_size,
            m.short_conv_kernel, m.layernorm_epsilon, m.rope_theta,
            m.max_position_embeddings, m.moe_routed_scaling_factor) == (
        body["hidden_size"], body["intermediate_size"],
        body["moe_intermediate_size"], body["num_attention_heads"],
        body["num_key_value_heads"], body["derived"]["head_dim"],
        body["published"]["num_experts"], body["num_experts_per_tok"],
        body["vocab_size"], body["conv_L_cache"], body["norm_eps"],
        body["rope_parameters"]["rope_theta"],
        body["max_position_embeddings"], body["routed_scaling_factor"])
    assert m.experts_held == body["num_experts"] and m.tie_embed_logits
    assert m.moe_selection_bias == body["use_expert_bias"]
    assert m.moe_normalize_gates == body["norm_topk_prob"]
    shapes = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    d = body["derived"]
    by_hand = (30 * d["conv_mixer_params"] + 10 * d["attention_mixer_params"]
               + 2 * d["dense_mlp_params"]
               + 38 * (8 * d["expert_params"] + 2048 * 64 + 64)
               + 65536 * 2048 + 81 * 2048)
    assert sum(a.size for a in jax.tree.leaves(shapes)) == by_hand \
        == 3761333888
    assert "3,761.3 M = 7.52 GB" in body["reduced_why"]


def test_bytes_by_hand():
    model = _model()
    assert flops_lfm2.layers_of(model, "conv") == 30
    assert flops_lfm2.layers_of(model, "full_attention") == 10
    # 2048 x 6144 in, 3 x 2048 of filter, 2048 x 2048 out, bf16
    assert flops_lfm2.mixer_weight_bytes(model) == 2 * 16783360 \
        == 2 * model["conv_mixer_params"]
    # two rows of 2,048 bf16 values
    assert flops_lfm2.tail_bytes(model) == 8192 \
        == model["tail_bytes_per_layer_and_sequence"]
    assert model["tail_bytes_per_sequence"] == 30 * 8192
    # ONE decode tick of 256 rows, each a run of its own: the weights once,
    # a row in and out, a tail read and written
    need = flops_lfm2.mixer_bytes(model, 1, 256, 256)
    assert need == 30 * (33566720 + 256 * 8192 + 2 * 256 * 8192)
    assert round(need / 1e9, 2) == 1.20
    # a 256-row prompt run beside them: one more run, 256 more rows
    more = flops_lfm2.mixer_bytes(model, 1, 257, 512) - need
    assert more == 30 * (256 * 8192 + 2 * 8192)
    # ten attention layers, 8 KV heads of 64, bf16: 20 KiB a token
    assert flops_lfm2.kv_bytes_per_token(model) == 20480 \
        == model["kv_bytes_per_token"]
    # an expert is THREE matrices
    cost = flops_lfm2.held_gemm_cost(model, 4096.0, 304.0)
    assert cost["flops"] == 2 * 4096 * 3 * 2048 * 1536
    assert cost["bytes"] == (304 * 3 * 2048 * 1536 + 2 * 4096 * 2048) * 2


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 30), _ev(3, t0 + 30, 10), _ev(4, t0 + 40, 10),
                     _ev(5, t0 + 50, 30)])


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
STARTS = (100, 220, 340)
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kOutput" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.3 = f32[8] fusion(%%p.3), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%%paged_attention.1 = f32[8] %s" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
}
""" % (" ".join(_ev(1, t, 100) for t in STARTS),
       " ".join(_tick_ops(t) for t in STARTS), PALLAS)

FWD = "jit(tick)/ragged-fwd/while/body/closed_call/"
OP_NAMES = {
    "fusion.1": FWD + "short_conv/in_proj/dot_general",
    "fusion.3": FWD + "short_conv/conv/add",
    "paged_attention.1": FWD + "attention/global/pallas_call",
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
        engine={"prefill_chunk": 256, "max_slots": 256})


def test_conv_share_reads_the_scope():
    # 3 ticks x (30 + 10 us under short_conv) of 3 x 80 us busy
    assert _reader("conv_share.lfm2").reduce(_run(OP_NAMES)) == \
        pytest.approx(50.0)


# 3 tokens received in the span: three decode rows, a run each; and a
# prompt of 600 tokens prefilled a third inside it: 599 rows in 3 ticks of
# at most 256 rows (the engine's cap for 256 slots)
DECODE = {"n_prompt": 300, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
          "sent_t": -9.0}
FRESH = {"n_prompt": 600, "token_t": [2.0], "sent_t": -1.0}


def test_conv_roofline_counts_ticks_runs_and_rows(capsys):
    got = _reader("conv_roofline.lfm2").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    # three executions in the capture, less one for the two it cuts
    need = flops_lfm2.mixer_bytes(_model(), 2, 3 + 3 / 3, 3 + 599 / 3)
    assert got == pytest.approx(100.0 * need / 819e9 / 120e-6)
    assert "2 ticks, 4 runs of 203 rows" in capsys.readouterr().out


def test_paged_roofline_counts_ten_layers(capsys):
    got = _reader("paged_attn_roofline.lfm2").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    # the three decode rows' contexts; a third of the prompt's chunks' keys
    keys = (301 + 302 + 303) + (256 + 512 + 600) / 3
    assert got == pytest.approx(100.0 * keys * 20480 / 819e9 / 30e-6)
    assert "ten attention layers" in capsys.readouterr().out


def test_rows_per_expert_divides_the_held_counters():
    run = _run(OP_NAMES)
    run.counters = {"mlt_engine_moe_held_assignments_total": 4864.0,
                    "mlt_engine_moe_held_experts_touched_total": 304.0}
    assert _reader("rows_per_expert.lfm2").reduce(run) == 16.0


def test_readers_report_nothing_without_their_source():
    bare = types.SimpleNamespace(trace=None, peaks=None, counters={},
                                 trace_host=None, all_samples=[], engine={},
                                 cell=types.SimpleNamespace(model=_model()))
    for name in READERS:
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scopes or the kernel (the
    # parent's), and a cell whose model has no conv layers
    plain = _run({})
    for name in ("conv_share.lfm2", "conv_roofline.lfm2",
                 "expert_gemm_roofline.lfm2", "rows_per_expert.lfm2"):
        assert _reader(name).reduce(plain) is None, name
    unnamed = CAPTURE.replace("paged_attention", "mamba_sweep")
    assert _reader("paged_attn_roofline.lfm2").reduce(
        _run({}, capture=unnamed)) is None
    other = _run(OP_NAMES)
    other.cell = types.SimpleNamespace(model={"hidden_size": 64})
    for name in ("conv_roofline.lfm2", "expert_gemm_roofline.lfm2",
                 "paged_attn_roofline.lfm2"):
        assert _reader(name).reduce(other) is None, name


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_correct_on_both_pools():
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths,
    four slots, probes of 24 and 40 tokens, 4 held experts of 16 from the
    fourth on, eight layers that end in a part period: the K/V pages and
    the tail slots serve them (no prefix cache, so the probes need hit
    nothing), the reference agrees at the emitted positions, and the
    counters the readers want are on /metrics."""
    from benchmark.lib import harness, serving

    cell = _cell()
    args = types.SimpleNamespace(seed=2147485019, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] is None and not c["prefix_hit_tokens"]
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 1e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    assert run.engine["prefill_chunk"] == 16
    assert _reader("state_rows_per_touch.brumby").reduce(run) > 1.0
    assert _reader("paged_rows_per_walk.batch").reduce(run) >= 1.0
    assert _reader("rows_per_expert.lfm2").reduce(run) > 0
    assert _reader("pool_dry_tick_share.batch").reduce(run) == 0.0
    assert _reader("pool_reclaim_ms.batch").reduce(run) is not None
    assert _reader("paged_fetch_share.batch").reduce(run) > 0
    assert run.counters["mlt_engine_state_resets_total"] > 0
    for name in ("mlt_engine_state_recomputed_tokens_total",
                 "mlt_engine_state_pool_bytes",
                 "mlt_engine_moe_held_assignments_total",
                 'mlt_engine_pool_pages{class="state",state="referenced"}',
                 'mlt_engine_pool_pages{class="full",state="referenced"}'):
        assert name in run.counters, name
