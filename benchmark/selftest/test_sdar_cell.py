"""The SDAR-30B-A3B-Chat cell's own pieces: its entries in the index (found
WHEREVER they stand: a later PR appends behind them), its configuration
against the catalog row, ``lib/flops_sdar.py`` against a hand count, its
seven readers on a hand-made capture and hand-made counters, the parent's
counters and a capture without the scopes or the kernel (nothing is reported,
nothing raises), and a CPU rehearsal of the cell that serves its probes by
block ticks, hits the prefix cache and compares correct.

The capture (microseconds from the lines' timestamp): the tick program runs
three times, 100-200, 220-320 and 340-440.  In each tick: one
`paged_attention` kernel of 20 us, an expert fusion of 30 us under
`moe/expert_gemm`, the head of 10 us under `lm_head_loss` and an unmasking
fusion of 20 us under `block_unmask`."""

import functools
import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_sdar, peaks, trace

CELL = "sdar30b_chat_blocks_closed"
US = 10 ** 6     # picoseconds
READERS = ("block_rows_per_token.sdar", "block_ticks_per_block.sdar",
           "unmask_tokens_per_step.sdar", "unmask_share.sdar",
           "paged_attn_roofline.sdar", "expert_gemm_roofline.sdar",
           "rows_per_expert.sdar")


@functools.cache
def _cell():
    return cells.Cell(CELL)


def _reader(name):
    return cells.Cell.reader_at(os.path.join(
        _cell().bench_dir, "layer_metrics", name + ".py"))


def _model():
    return _cell().model


def test_the_cells_entries_are_in_the_index_once_each():
    index = _cell().bench
    assert len(json.dumps(index, indent=1)) < 64 * 1024
    assert [c["name"] for c in index["configs"]].count(
        "sdar-30b-a3b-chat") == 1
    assert [w["name"] for w in index["workloads"]].count(CELL) == 1
    entry = _cell().entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "sdar-30b-a3b-chat", "chat_rag_closed_blocks", 1)
    config, = [c for c in index["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == ["num_experts", "vocab_size"]
    for item in (config, entry):
        assert 1 <= len(item["why"]) <= 200
    names = [m["name"] for m in index["per_layer"]]
    for name in READERS:
        assert names.count(name) == 1, name
    accepted = ("falcon7b_batch_decode", "lfm2_24b_chat_closed")
    for group in ("end_to_end", "per_layer"):
        for m in index[group]:
            named = m.get("workloads", [])
            assert named.count(CELL) <= 1, m["name"]
            if CELL in named:
                assert all(named.index(w) < named.index(CELL)
                           for w in accepted if w in named), m["name"]
    # every per-layer metric that moves decode_tokens_per_s lists its cells
    assert all("workloads" in m for m in index["per_layer"]
               if m["moves"] == "decode_tokens_per_s")


def test_the_configuration_is_the_catalog_row_but_for_its_share():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    cell = _cell()
    body = cell.config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items()
               if body.get(k, "absent") != v]
    assert differs == body["reduced"] == ["num_experts", "vocab_size"]
    assert body["published"] == {
        "num_experts": row["config"]["num_experts"],
        "vocab_size": row["config"]["vocab_size"], "mask_token_id": 151669}
    # NO depth cut, no width among the cuts: 16 of 128 experts, an eighth of
    # the vocabulary
    assert body["num_hidden_layers"] == 48
    assert (body["num_experts"], body["vocab_size"]) == (16, 18992)
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    f = body["flags"]
    assert f["model_name"] == body["preset"] == "sdar-30b-a3b-chat"
    assert f["moe_experts_held"] == body["num_experts"]
    assert f["moe_first_held_expert"] == 0
    assert f["moe_capacity_factor"] == 128 / 16        # nothing is dropped
    assert f["vocab_size"] == body["vocab_size"]
    assert f["mask_token_id"] == body["vocab_size"] - 1 \
        == body["derived"]["mask_token_id"]
    assert not {"num_layers", "hidden_size", "ffn_hidden_size",
                "moe_ffn_hidden_size", "num_experts", "kv_channels",
                "diffusion_block_length"} & set(f)
    assert sorted(set(f) - {"model_name", "params_dtype", "tokenizer_type"}) \
        == sorted(body["changed_from_preset"])
    d = body["derived"]
    assert d["router_width"] == 128 and d["diffusion_block_length"] == 4
    assert d["expert_params"] == 3 * 2048 * 768
    assert d["kv_bytes_per_token"] == 98304 and d["page_tokens"] == 16
    for key in ("layer", "qk_norm", "mask", "block_length", "generation",
                "defaults", "mask_token_id", "router", "residual", "why"):
        assert key in body["assumed"], key
    for key in ("deployment", "reduced_why", "flags_why"):
        assert len(body[key]) > 200, key
    assert "8 chips that share each layer" in body["deployment"]
    tol = body["tolerance"]
    assert "why" in tol and 0 < tol["mean_abs_nats"] < tol["max_abs_nats"]
    # the traffic file holds exactly ISSUE 59's parameters
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["ramp_s"], mix["plan_requests"],
            mix["shared_prefix"]) == ("closed_loop", 64, 30, 1024, None)
    assert mix["clients"] == 2 * f["max_batch_slots"] == 64
    assert mix["draw_seed"] not in (20261001, 20261002)    # of its own
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.9, "min": 32, "max": 4096}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["sampling"] == {
        "top_k": 1, "use_eod_token_for_early_termination": False,
        "remasking_strategy": "sequential", "denoising_steps": 4}
    assert mix["probe_lengths"] == [602, 841]
    assert [n % 4 for n in mix["probe_lengths"]] == [2, 1]
    assert all((n + 32) % 4 for n in mix["probe_lengths"])   # end mid-block
    assert (841 - 1) // 16 * 16 == 832 and (832 - 8) % 4 == 0
    assert mix["trace_seconds"] in (1, 2, 3) and "trace_why" in mix
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] + 32 \
        <= f["engine_max_seq"] == 5152
    assert f["prefill_chunk"] % 4 == 0 and f["engine_max_seq"] % 4 == 0
    for name in READERS:
        entry, = [m for m in cell.bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL], name
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
    reported = {m["name"] for m in cell.per_layer}
    assert {"tick_ms.batch", "moe_share.joyai", "copy_share.batch",
            "paged_rows_per_walk.batch", "paged_fetch_share.batch",
            "pool_dry_tick_share.batch", "host_work_ms.batch",
            "prefill_tick_share.batch", "slot_occupancy.batch",
            "setup_trace_lower_s"} <= reported
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tokens_per_s", "setup_s"}


def test_the_preset_is_the_configuration_and_the_bytes_are_the_trees():
    import jax

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.models import init_model_params

    cell = _cell()
    body = cell.config
    cfg = parse_args(cell.flags({"seed": 1}))
    m = cfg.model
    assert cfg.model_name == "sdar_moe"
    assert (m.num_layers, m.hidden_size, m.ffn_hidden_size,
            m.moe_ffn_hidden_size, m.num_attention_heads,
            m.num_attention_heads_kv, m.kv_channels, m.num_experts,
            m.moe_router_topk, m.vocab_size, m.layernorm_epsilon,
            m.rope_theta, m.max_position_embeddings) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["intermediate_size"], body["moe_intermediate_size"],
        body["num_attention_heads"], body["num_key_value_heads"],
        body["head_dim"], body["published"]["num_experts"],
        body["num_experts_per_tok"], body["vocab_size"],
        body["rms_norm_eps"], body["rope_theta"],
        body["max_position_embeddings"])
    assert m.experts_held == body["num_experts"] and not m.tie_embed_logits
    assert m.moe_normalize_gates == body["norm_topk_prob"]
    assert m.diffusion_block_length == 4 and m.mask_token_id == 18991
    shapes = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    d = body["derived"]
    assert d["attention_params"] == 2048 * 5120 + 4096 * 2048 + 2 * 128
    assert d["layer_params"] == (d["attention_params"] + d["router_params"]
                                 + 16 * d["expert_params"] + 2 * 2048)
    by_hand = 48 * d["layer_params"] + 2 * d["padded_vocab_size"] * 2048 + 2048
    assert sum(a.size for a in jax.tree.leaves(shapes)) == by_hand \
        == d["total_params"] == 4620761088
    assert "4,620.8 M = 9.24 GB" in body["reduced_why"]
    # the pool: 2,561 pages of 16 tokens at 96 KiB a token
    assert cell.config["flags"]["kv_pool_pages"] * 16 * 98304 == 4028104704


def test_bytes_by_hand():
    model = _model()
    # 48 layers, 4 KV heads of 128, K and V, bf16: 96 KiB a token
    assert flops_sdar.kv_bytes_per_token(model) == 98304 \
        == model["kv_bytes_per_token"]
    # a whole block at one token a step: 4 steps and a commit, 4 rows each
    assert flops_sdar.block_rows(model, 1, 4) == 20
    # an expert is THREE matrices of 2048 x 768
    cost = flops_sdar.held_gemm_cost(model, 4096.0, 768.0)
    assert cost["flops"] == 2 * 4096 * 3 * 2048 * 768
    assert cost["bytes"] == (768 * 3 * 2048 * 768 + 2 * 4096 * 2048) * 2
    # three tokens received in the span at contexts 301-303: three steps,
    # each its sequence once; a prompt of 600 prefilled a third inside it
    assert flops_sdar.needed_keys([DECODE, FRESH], (0.0, 1.0), 64) == \
        (301 + 302 + 303) + sum(
            min(e, 600) for e in range(64, 664, 64)) / 3


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 20), _ev(3, t0 + 20, 30), _ev(4, t0 + 50, 10),
                     _ev(5, t0 + 60, 20)])


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
STARTS = (100, 220, 340)
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%paged_attention.1 = f32[8] %s" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kOutput" } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kOutput" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.3 = f32[8] fusion(%%p.3), kind=kLoop" } }
}
""" % (" ".join(_ev(1, t, 100) for t in STARTS),
       " ".join(_tick_ops(t) for t in STARTS), PALLAS)

FWD = "jit(tick)/decode-fwd/while/body/closed_call/"
OP_NAMES = {
    "paged_attention.1": FWD + "attention/global/pallas_call",
    "fusion.1": FWD + "moe/expert_gemm/dot_general",
    "fusion.2": "jit(tick)/lm_head_loss/dot_general",
    "fusion.3": "jit(tick)/block_unmask/reduce_max",
}
DECODE = {"n_prompt": 300, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
          "sent_t": -9.0, "status": 200, "error": None}
FRESH = {"n_prompt": 600, "token_t": [2.0], "sent_t": -1.0, "status": 200,
         "error": None}


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
        samples=list(samples), t_open=0.0, t_close=1.0,
        engine={"prefill_chunk": 64, "max_slots": 32})


def test_unmask_share_reads_both_scopes():
    # 3 ticks x (20 us under block_unmask + 10 under lm_head_loss) of
    # 3 x 80 us busy
    assert _reader("unmask_share.sdar").reduce(_run(OP_NAMES)) == \
        pytest.approx(37.5)
    # a program without the block scope says nothing, whatever its head
    bare = {k: v for k, v in OP_NAMES.items() if k != "fusion.3"}
    assert _reader("unmask_share.sdar").reduce(_run(bare)) is None


def test_paged_roofline_counts_a_steps_sequence_once(capsys):
    got = _reader("paged_attn_roofline.sdar").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    keys = (301 + 302 + 303) + sum(
        min(e, 600) for e in range(64, 664, 64)) / 3
    assert got == pytest.approx(100.0 * keys * 98304 / 819e9 / 60e-6)
    assert "a step's sequence once" in capsys.readouterr().out


def test_the_counter_readers_divide_their_counters():
    run = _run(OP_NAMES, [DECODE])
    run.counters = {
        "mlt_engine_block_denoise_rows_total": 48.0,
        "mlt_engine_block_commit_rows_total": 8.0,
        "mlt_engine_block_steps_total": 12.0,
        "mlt_engine_block_slot_ticks_total": 12.0,
        "mlt_engine_blocks_committed_total": 3.0,
        "mlt_engine_block_tokens_unmasked_total": 12.0,
        "mlt_engine_ticked_tokens_total": 12.0,
        "mlt_engine_moe_held_assignments_total": 6144.0,
        "mlt_engine_moe_held_experts_touched_total": 768.0}
    # three blocks of 4 at a token a step, two of them committed
    assert _reader("block_rows_per_token.sdar").reduce(run) == \
        pytest.approx(56 / 12)
    assert _reader("block_ticks_per_block.sdar").reduce(run) == 4.0
    assert _reader("unmask_tokens_per_step.sdar").reduce(run) == 1.0
    assert _reader("rows_per_expert.sdar").reduce(run) == 8.0


def test_readers_report_nothing_without_their_source():
    bare = types.SimpleNamespace(
        trace=None, peaks=None, counters={}, trace_host=None, all_samples=[],
        samples=[], t_open=0.0, t_close=1.0, engine={},
        cell=types.SimpleNamespace(model=_model()))
    for name in READERS:
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scopes or the kernel (the
    # parent's), the parent's counters, and a cell of another model
    plain = _run({}, [DECODE])
    plain.counters = {"mlt_engine_ticks_total": 9.0}
    for name in ("block_rows_per_token.sdar", "block_ticks_per_block.sdar",
                 "unmask_tokens_per_step.sdar", "unmask_share.sdar",
                 "expert_gemm_roofline.sdar", "rows_per_expert.sdar"):
        assert _reader(name).reduce(plain) is None, name
    unnamed = CAPTURE.replace("paged_attention", "mamba_sweep")
    assert _reader("paged_attn_roofline.sdar").reduce(
        _run({}, capture=unnamed)) is None
    other = _run(OP_NAMES, [DECODE])
    other.cell = types.SimpleNamespace(model={"hidden_size": 64})
    other.counters = {"mlt_engine_moe_held_assignments_total": 8.0,
                      "mlt_engine_moe_held_experts_touched_total": 2.0}
    for name in ("paged_attn_roofline.sdar", "expert_gemm_roofline.sdar",
                 "rows_per_expert.sdar"):
        assert _reader(name).reduce(other) is None, name


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_correct_by_block_ticks():
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths,
    four slots, probes of 26 and 41 tokens (remainders 2 and 1), 4 held
    experts of 16 from the fourth on: the probes hit the prefix cache, the
    two-stream reference agrees at the emitted positions, and the counters
    the readers want are on /metrics."""
    from benchmark.lib import harness, serving

    cell = _cell()
    args = types.SimpleNamespace(seed=2147485019, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] > 0
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 2e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    assert 4.0 < _reader("block_rows_per_token.sdar").reduce(run) < 7.0
    assert 3.0 < _reader("block_ticks_per_block.sdar").reduce(run) < 6.5
    assert _reader("unmask_tokens_per_step.sdar").reduce(run) == \
        pytest.approx(1.0)
    assert _reader("rows_per_expert.sdar").reduce(run) > 0
    assert _reader("paged_rows_per_walk.batch").reduce(run) >= 1.0
    assert 0 < _reader("paged_fetch_share.batch").reduce(run) <= 100.0
    assert _reader("slot_occupancy.batch").reduce(run) > 0
    assert _reader("pool_dry_tick_share.batch").reduce(run) is not None
    for name in ("mlt_engine_block_recomputed_total",
                 "mlt_engine_block_commit_rows_total",
                 "mlt_engine_moe_held_assignments_total"):
        assert name in run.counters, name
