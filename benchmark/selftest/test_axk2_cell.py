"""The A.X-K2 cell's own pieces: its entries in the index (found WHEREVER
they stand: a later PR appends behind them), its configuration against the
catalog row, its bytes against the program's own tree, ``lib/flops_axk2.py``
against hand counts at two sizes, its readers on a hand-made capture and
hand-made counters, the parent's counters and a capture without the scopes
(nothing is reported, nothing raises), and a CPU rehearsal of the cell that
serves its probes past ``index_topk``, hits the prefix cache and compares
correct.

The capture (microseconds from the lines' timestamp): the tick program runs
three times, 100-200, 220-320 and 340-440.  In each tick: a sweep fusion of
30 us under `index_score`, a selection fusion of 10 us under `index_select`,
a gather of 5 us under `sparse_gather`, an attention fusion of 15 us under
`sparse_attention`, and an expert fusion of 20 us under `moe/expert_gemm`:
80 us busy a tick."""

import functools
import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_axk2, peaks, trace

CELL = "axk2_docqa_32k_closed"
US = 10 ** 6     # picoseconds
READERS = ("index_score_roofline.axk2", "sparse_attn_roofline.axk2",
           "index_select_share.axk2", "sparse_share.axk2",
           "keys_attended_share.axk2", "expert_gemm_roofline.axk2",
           "rows_per_expert.axk2")
SHARED = ("tick_ms.batch", "host_gap_ms.batch", "slot_occupancy.batch",
          "idle_apply_share.batch", "idle_plan_share.batch",
          "idle_write_share.batch", "idle_unattributed_share.batch",
          "host_work_ms.batch", "prefill_tick_share.batch",
          "pool_dry_tick_share.batch", "pool_reclaim_ms.batch",
          "plan_upload_ms.batch", "host_offcpu_ms.batch", "copy_share.batch",
          "moe_share.joyai", "setup_wall_s", "setup_backend_s",
          "setup_compile_s", "setup_cold_compile_s", "setup_trace_lower_s")


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
    assert [c["name"] for c in index["configs"]].count("a.x-k2") == 1
    assert [w["name"] for w in index["workloads"]].count(CELL) == 1
    entry = _cell().entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "a.x-k2", "docqa_prefix32k", 1)
    config, = [c for c in index["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for item in (config, entry):
        assert 1 <= len(item["why"]) <= 200
    names = [m["name"] for m in index["per_layer"]]
    cell = _cell()
    for name in READERS:
        assert names.count(name) == 1, name
        listed, = [m for m in index["per_layer"] if m["name"] == name]
        assert listed["workloads"] == [CELL], name
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            listed["layer"], listed["unit"], listed["moves"],
            listed["source"])
    for group in ("end_to_end", "per_layer"):
        for m in index[group]:
            named = m.get("workloads", [])
            assert named.count(CELL) <= 1, m["name"]
            if CELL in named:
                assert all(named.index(w) < named.index(CELL) for w in (
                    "falcon7b_batch_decode", "ouro26b_shortqa_closed")
                    if w in named), m["name"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(SHARED) | set(READERS) == reported
    # no paged kernel walks a page here: its readers would read nothing
    assert not {"paged_rows_per_walk.batch", "paged_fetch_share.batch",
                "paged_attn_roofline.batch"} & reported
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tokens_per_s", "setup_s"}


def test_the_configuration_is_the_catalog_row_but_for_its_cuts():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K2")
    cell = _cell()
    body = cell.config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items()
               if body.get(k, "absent") != v]
    assert sorted(differs) == sorted(body["reduced"])
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"]) == (5, 8, 20480)
    assert body["rope_parameters"] == row["config"]["rope_parameters"]
    f = body["flags"]
    assert f["model_name"] == body["preset"] == "a.x-k2"
    assert f["moe_experts_held"] == body["n_routed_experts"] == 8
    assert f["moe_capacity_factor"] == 256 / 8          # nothing is dropped
    assert (f["max_batch_slots"], f["engine_max_seq"], f["kv_pool_pages"],
            f["num_layers"]) == (128, 34816, 20481, 4)
    assert not {"hidden_size", "ffn_hidden_size", "moe_ffn_hidden_size",
                "num_experts", "index_topk", "index_n_heads",
                "index_head_dim", "q_lora_rank", "kv_lora_rank",
                "moe_n_group", "moe_topk_group"} & set(f)
    assert sorted(set(f) - {"model_name", "params_dtype", "tokenizer_type"}) \
        == sorted(body["changed_from_preset"])
    d = body["derived"]
    assert d["router_width"] == 256 and d["first_held_expert"] == 0
    assert d["cache_bytes_per_token_per_layer"] == (640 + 128) * 2
    assert d["expert_params"] == 3 * 7168 * 2048
    for key in ("gated_norm", "attention_output_gate", "rope", "indexer",
                "router"):
        assert len(body["assumed"][key]) > 200, key
    assert "Not chosen" in body["assumed"]["gated_norm"]
    assert "Not chosen" in body["assumed"]["attention_output_gate"]
    for key in ("deployment", "reduced_why", "flags_why"):
        assert len(body[key]) > 200, key
    assert "One chip of 32 that share each layer" in body["deployment"]
    tol = body["tolerance"]
    assert "why" in tol and 0 < tol["mean_abs_nats"] < tol["max_abs_nats"]
    # the traffic file holds exactly ISSUE 67's parameters
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["ramp_s"],
            mix["plan_requests"]) == ("closed_loop", 256, 20, 4096)
    assert mix["clients"] == 2 * f["max_batch_slots"]
    assert mix["shared_prefix"]["share"] == 1.0
    assert (mix["shared_prefix"]["count"], mix["shared_prefix"]["tokens"]) \
        == (4, 32768)
    assert (mix["shared_prefix"]["prime"]["together"],
            mix["shared_prefix"]["prime"]["min_hit_share"]) == (2, 0.9)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 32, "max": 1024}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 384}
    assert mix["prompt_max"] == 33792 and mix["sampling"] == {"top_k": 1}
    assert mix["probe_lengths"] == [4352, 4608]
    assert min(mix["probe_lengths"]) > body["index_topk"]
    assert mix["prompt_max"] + mix["output_len"]["max"] \
        <= f["engine_max_seq"]
    # the probes warm every compiled tick shape: no prompt rows, and the cap
    assert -(-f["max_batch_slots"] // f["prefill_chunk"]) == 1


def test_the_preset_is_the_configuration_and_the_bytes_are_the_trees():
    import jax

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.generation.pools import memory_kind
    from megatron_llm_tpu.models import init_model_params

    cell = _cell()
    body = cell.config
    cfg = parse_args(cell.flags({"seed": 1}))
    m = cfg.model
    rp = body["rope_parameters"]
    assert (m.hidden_size, m.ffn_hidden_size, m.moe_ffn_hidden_size,
            m.num_attention_heads, m.q_lora_rank, m.kv_lora_rank,
            m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
            m.index_n_heads, m.index_head_dim, m.index_topk,
            m.num_experts, m.moe_router_topk, m.moe_n_group,
            m.moe_topk_group, m.moe_routed_scaling_factor,
            m.moe_shared_experts, m.gated_norm_rank, m.layernorm_epsilon,
            m.max_position_embeddings, m.dense_prefix_layers) == (
        body["hidden_size"], body["intermediate_size"],
        body["moe_intermediate_size"], body["num_attention_heads"],
        body["q_lora_rank"], body["kv_lora_rank"], body["qk_nope_head_dim"],
        body["qk_rope_head_dim"], body["v_head_dim"], body["index_n_heads"],
        body["index_head_dim"], body["index_topk"],
        body["published"]["n_routed_experts"], body["num_experts_per_tok"],
        body["n_group"], body["topk_group"], body["routed_scaling_factor"],
        body["n_shared_experts"], body["gated_norm_rank"],
        body["rms_norm_eps"], body["max_position_embeddings"],
        body["first_k_dense_replace"])
    assert (m.rope_theta, m.rope_scaling_factor, m.rope_yarn_beta_fast,
            m.rope_yarn_beta_slow, m.rope_yarn_original_max_position,
            m.rope_yarn_mscale_all_dim) == (
        rp["rope_theta"], rp["factor"], rp["beta_fast"], rp["beta_slow"],
        rp["original_max_position_embeddings"], rp["mscale_all_dim"])
    assert m.gated_norm == body["gated_norm"]
    assert m.attention_output_gate == body["attention_output_gate"]
    assert m.depth == body["num_hidden_layers"]
    assert m.experts_held == body["n_routed_experts"]
    assert memory_kind(cfg) == "indexed"
    shapes = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 2863064320
    assert "2,863.06 M = 5.73 GB" in body["reduced_why"]
    pool = 20481 * 16 * 5 * body["derived"]["cache_bytes_per_token_per_layer"]
    assert pool == 2516705280 and "2.52 GB" in body["reduced_why"]
    assert (2863064320 * 2 + pool) / 16909336064 > 0.25


# ---- operations and bytes by hand, at two sizes -----------------------------

def test_flops_by_hand_at_the_published_sizes():
    model = _model()
    c = 33400.0
    sweep = flops_axk2.sweep_cost(model, c)
    # a key: 64 heads x 128 multiply-adds, a ReLU and a weighted add a head;
    # 256 bytes of key read, 4 bytes of score written; the row's 16 KiB query
    assert sweep["flops"] == c * (2 * 64 * 128 + 2 * 64)
    assert sweep["bytes"] == c * 256 + 64 * 128 * 2 + c * 4
    assert flops_axk2.picked(model, c) == 2048
    assert flops_axk2.picked(model, 1500.0) == 1500
    assert flops_axk2.gather_cost(model, c) == {
        "flops": 0.0, "bytes": 2048 * 576 * 2}
    att = flops_axk2.attention_cost(model, c)
    assert att["flops"] == 2048 * 64 * 2 * (576 + 512)
    sel = flops_axk2.select_cost(model, c)
    assert sel["bytes"] == c * 4 + 2048 * 4
    # ONE tick of 128 rows at 33.4k keys over 5 layers: the issue's figures
    rows = [(c, 128.0, 128.0)]          # decode rows: each its own reader
    need = flops_axk2.total(flops_axk2.sweep_cost, model, rows)
    assert round(need["bytes"] / 1e9, 1) == 5.6
    assert round(need["flops"] / 1e12, 2) == 0.35
    got = flops_axk2.total(flops_axk2.gather_cost, model, rows)
    assert round(got["bytes"] / 1e9, 2) == 1.51      # 1,152 needed bytes a row
    assert round(flops_axk2.total(flops_axk2.attention_cost, model,
                                  rows)["flops"] / 1e9) == 183


def test_flops_by_hand_at_tiny_sizes():
    model = {"index_n_heads": 2, "index_head_dim": 4, "index_topk": 3,
             "num_attention_heads": 2, "kv_lora_rank": 8,
             "qk_rope_head_dim": 2, "num_hidden_layers": 2}
    assert flops_axk2.sweep_cost(model, 5) == {
        "flops": 5 * (2 * 2 * 4 + 2 * 2), "bytes": 5 * 8 + 16 + 20}
    # a context of 2 attends both keys; of 5, three
    assert flops_axk2.gather_cost(model, 2)["bytes"] == 2 * 10 * 2
    assert flops_axk2.gather_cost(model, 5)["bytes"] == 3 * 10 * 2
    assert flops_axk2.attention_cost(model, 5)["flops"] == 3 * 2 * 2 * 18
    both = flops_axk2.total(flops_axk2.gather_cost, model,
                            [(2, 1.0, 1.0), (5, 2.0, 2.0)])
    assert both["bytes"] == 2 * (40 + 2 * 60)
    # a chunk of 4 rows at 5 keys needs its keys ONCE (5 x 8 bytes, the
    # rows' queries and scores apart) and no more latent rows than 5
    chunk = [(5, 4.0, 1.0)]
    assert flops_axk2.total(flops_axk2.sweep_cost, model, chunk) == {
        "flops": 2 * 4 * 5 * 20, "bytes": 2 * 76.0}
    assert flops_axk2.total(flops_axk2.gather_cost, model,
                            chunk)["bytes"] == 2 * 5 * 20


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 30), _ev(3, t0 + 30, 10), _ev(4, t0 + 40, 5),
                     _ev(5, t0 + 45, 15), _ev(6, t0 + 60, 20)])


STARTS = (100, 220, 340)
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kOutput" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%%gather.1 = bf16[8] gather(%%p.3)" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.3 = f32[8] fusion(%%p.4), kind=kOutput" } }
  event_metadata { key: 6 value { id: 6 name: "%%fusion.4 = f32[8] fusion(%%p.5), kind=kOutput" } }
}
""" % (" ".join(_ev(1, t, 100) for t in STARTS),
       " ".join(_tick_ops(t) for t in STARTS))

FWD = "jit(tick)/ragged-fwd/while/body/closed_call/attention/mla/"
OP_NAMES = {
    "fusion.1": FWD + "index_score/while/body/dot_general",
    "fusion.2": FWD + "index_select/while/body/reduce_sum",
    "gather.1": FWD + "while/body/sparse_gather/gather",
    "fusion.3": FWD + "while/body/sparse_attention/dot_general",
    "fusion.4": "jit(tick)/ragged-fwd/while/body/moe/expert_gemm/dot_general",
}


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


def _run(op_names, samples=(), span=(0.0, 1.0), capture=CAPTURE):
    reduced = trace.reduce_profile(_profile(capture), op_names)
    reduced.path = ""
    cell = types.SimpleNamespace(model=_model(), traffic=_cell().traffic)
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, counters={}, trace_host=span, all_samples=list(samples),
        engine={"prefill_chunk": 512, "max_slots": 128, "page_size": 16})


def test_the_shares_read_their_scopes():
    run = _run(OP_NAMES)
    # 10 us of selection in 80 us busy a tick; 60 us of the four scopes
    assert _reader("index_select_share.axk2").reduce(run) == \
        pytest.approx(12.5)
    assert _reader("sparse_share.axk2").reduce(run) == pytest.approx(75.0)


# 3 tokens received in the span: three decode rows at 33,001-33,003 keys;
# a prompt of 33,000 tokens behind a primed prefix (32,752 of its tokens
# served by the cache) prefilled a HALF inside the span: 247 rows at a mean
# context of 32,876
DECODE = {"n_prompt": 33000, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
          "sent_t": -9.0, "prefix": 1}
FRESH = {"n_prompt": 33000, "token_t": [1.5], "sent_t": -0.5, "prefix": 2}


def test_the_rooflines_count_rows_from_the_samples(capsys):
    model = _model()
    run = _run(OP_NAMES, [DECODE, FRESH])
    rows = flops_axk2.span_rows(run)
    assert rows[:3] == [(33001.0, 1.0, 1.0), (33002.0, 1.0, 1.0),
                        (33003.0, 1.0, 1.0)]
    # 247 prompt rows are ONE chunk of 512: its keys are needed once
    assert rows[3] == (32752 + 248 / 2.0, 0.5 * 247, 0.5 * 1)
    got = _reader("index_score_roofline.axk2").reduce(run)
    need = flops_axk2.total(flops_axk2.sweep_cost, model, rows)
    least = max(need["bytes"] / 819e9, need["flops"] / 197e12)
    assert got == pytest.approx(100.0 * least / 90e-6)
    # three decode rows read 3 x 33k keys; half a chunk's 123.5 rows read
    # theirs half a time and are priced by their operations
    assert need["bytes"] < 4 * 33003 * (256 + 4) * 5 + 1e6
    assert "index sweep" in capsys.readouterr().out
    got = _reader("sparse_attn_roofline.axk2").reduce(run)
    g = flops_axk2.total(flops_axk2.gather_cost, model, rows)
    a = flops_axk2.total(flops_axk2.attention_cost, model, rows)
    least = max((g["bytes"] + a["bytes"]) / 819e9, a["flops"] / 197e12)
    assert got == pytest.approx(100.0 * least / 60e-6)


def test_the_counters_divide():
    run = _run(OP_NAMES)
    run.counters = {"mlt_engine_sparse_keys_scored_total": 5 * 128 * 33400.0,
                    "mlt_engine_sparse_keys_attended_total": 5 * 128 * 2048.0,
                    "mlt_engine_moe_held_assignments_total": 640.0,
                    "mlt_engine_moe_held_experts_touched_total": 160.0}
    assert _reader("keys_attended_share.axk2").reduce(run) == \
        pytest.approx(100 * 2048 / 33400)
    assert _reader("rows_per_expert.axk2").reduce(run) == 4.0


def test_readers_report_nothing_without_their_source():
    bare = types.SimpleNamespace(trace=None, peaks=None, counters={},
                                 trace_host=None, all_samples=[], engine={},
                                 cell=types.SimpleNamespace(model=_model()))
    for name in READERS:
        assert _reader(name).reduce(bare) is None, name
    # a capture of a program without the scopes (the parent's), its counters
    plain = _run({}, [DECODE])
    for name in READERS:
        assert _reader(name).reduce(plain) is None, name
    other = _run(OP_NAMES, [DECODE])
    other.cell = types.SimpleNamespace(model={"hidden_size": 64}, traffic={})
    for name in ("index_score_roofline.axk2", "sparse_attn_roofline.axk2"):
        assert _reader(name).reduce(other) is None, name


# ---- the cell, rehearsed ------------------------------------------------------

def test_the_cell_rehearses_correct_through_the_indexed_pool():
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths,
    four slots, probes of 72 and 88 tokens past an ``index_topk`` of 32, 4
    held experts of 16 from the fourth on in 4 groups of which 2 stay,
    three primed prefixes of 128 tokens: every probe selects, the prefix
    cache serves latent rows and index keys together, the reference agrees
    at the emitted positions, and the counters the readers want are on
    /metrics."""
    from benchmark.lib import harness, serving

    cell = _cell()
    args = types.SimpleNamespace(seed=2147485019, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] > 0 and c["prefixes_hit"]
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 1e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    share = _reader("keys_attended_share.axk2").reduce(run)
    assert 0 < share < 100
    assert _reader("rows_per_expert.axk2").reduce(run) > 0
    assert _reader("pool_dry_tick_share.batch").reduce(run) is not None
    assert run.counters["mlt_engine_sparse_rows_total"] > 0
    assert not run.counters.get("mlt_engine_paged_walks_total")
    for name in ("mlt_engine_sparse_keys_scored_total",
                 "mlt_engine_sparse_keys_attended_total",
                 "mlt_engine_moe_held_assignments_total",
                 'mlt_engine_pool_pages{class="full",state="referenced"}'):
        assert name in run.counters, name
