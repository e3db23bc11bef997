"""The Ouro-2.6B cell's own pieces: its entries in the index (found WHEREVER
they stand: a later PR appends behind them), its configuration against the
catalog row (nothing reduced), ``lib/flops_ouro.py`` against a hand count,
its four readers on a hand-made capture and hand-made counters, the parent's
counters and a capture without the scope or the kernel (nothing is reported,
nothing raises), and a CPU rehearsal of the cell that serves its probes
through every pass, hits the prefix cache and compares correct.

The capture (microseconds from the lines' timestamp): the tick runs four
times, 100-200, 220-320, 340-440 and 460-560: the DECODE program
`jit_tick(77)`, again, the PREFILL program `jit_tick(78)`, then 77; the
capture cuts the first and the last, so one whole tick of each.  Both
programs number their instructions alike and fill them differently, as the
chip's do.  In each tick, under `loop_pass`: one `paged_attention` kernel of
20 us, `fusion.1` of 40 us and `fusion.2` of 10 us; `fusion.3`, the norm and
gate between passes, 5 us under `loop_norm_gate`; the GLU `fc1` kernel 10 us
under `loop_pass` behind its own jitted call's name.  In 77 `fusion.1` is a
projection (a body with a `convolution`) and `fusion.2` a norm; in 78
`fusion.1` is a layout copy and `fusion.2` a projection whose root is a
`squeeze`.  The label by name alone (`OP_NAMES`, what ``lib/trace.py``
gives) is 77's in both.  The programs' tables are written beside the capture
as the profiler embeds them: an ``HloProto`` a program in the plane
`/host:metadata`."""

import functools
import json
import os
import types
import warnings

import pytest

from benchmark.lib import cells, flops_ouro, hlo_modules, peaks, trace

CELL = "ouro26b_shortqa_closed"
US = 10 ** 6     # picoseconds
READERS = ("loop_passes_per_token.ouro", "loop_gemm_roofline.ouro",
           "paged_attn_roofline.ouro", "paged_attn_share.ouro")
SHARED = ("tick_ms.batch", "host_gap_ms.batch", "slot_occupancy.batch",
          "idle_apply_share.batch", "idle_plan_share.batch",
          "idle_write_share.batch", "idle_unattributed_share.batch",
          "host_work_ms.batch", "prefill_tick_share.batch",
          "pool_dry_tick_share.batch", "pool_reclaim_ms.batch",
          "plan_upload_ms.batch", "host_offcpu_ms.batch",
          "paged_rows_per_walk.batch", "copy_share.batch", "setup_wall_s",
          "setup_backend_s", "setup_compile_s", "setup_cold_compile_s",
          "setup_trace_lower_s", "paged_fetch_share.batch")


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
    assert [c["name"] for c in index["configs"]].count("ouro-2.6b") == 1
    assert [w["name"] for w in index["workloads"]].count(CELL) == 1
    entry = _cell().entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ouro-2.6b", "shortqa_closed", 1)
    config, = [c for c in index["configs"] if c["name"] == entry["config"]]
    assert config["reduced"] == []
    for item in (config, entry):
        assert 1 <= len(item["why"]) <= 200
    names = [m["name"] for m in index["per_layer"]]
    for name in READERS:
        assert names.count(name) == 1, name
    for group in ("end_to_end", "per_layer"):
        for m in index[group]:
            named = m.get("workloads", [])
            assert named.count(CELL) <= 1, m["name"]
            if CELL in named and "sdar30b_chat_blocks_closed" in named:
                assert named.index("sdar30b_chat_blocks_closed") \
                    < named.index(CELL), m["name"]
    cell = _cell()
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(SHARED) | set(READERS) and len(SHARED) == 21
    # Falcon's byte count is Falcon's own: 48 layers are not 192 slots
    assert "paged_attn_roofline.batch" not in reported
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tokens_per_s", "setup_s"}
    for name in READERS:
        entry, = [m for m in index["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL], name
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])


def test_the_configuration_is_the_catalog_row_whole():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cell = _cell()
    body = cell.config
    assert body["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items()
               if body.get(k, "absent") != v]
    assert differs == body["reduced"] == [] and body["published"] == {}
    assert (body["total_ut_steps"], body["early_exit_threshold"]) == (4, 1)
    f = body["flags"]
    assert f["model_name"] == body["preset"] == "ouro-2.6b"
    # no flag names a width, the depth, the vocabulary or the pass count
    assert set(f) == {"model_name", "params_dtype", "tokenizer_type",
                      "max_batch_slots", "engine_max_seq", "kv_pool_pages",
                      "prefill_chunk"}
    assert sorted(set(f) - {"model_name", "params_dtype", "tokenizer_type"}) \
        == sorted(body["changed_from_preset"])
    assert (f["max_batch_slots"], f["engine_max_seq"], f["prefill_chunk"]) \
        == (16, 576, 64)
    d = body["derived"]
    assert (d["cache_layer_slots"], d["kv_bytes_per_token"],
            d["page_tokens"]) == (192, 1572864, 16)
    for key in ("why", "biases", "qk_norm", "rope", "layer", "passes", "keys",
                "exit_gate", "norm_leaves"):
        assert key in body["assumed"], key
    assert "ONE 16 GB chip; nothing sharded" in body["deployment"]
    for key in ("reduced_why", "flags_why"):
        assert len(body[key]) > 200, key
    tol = body["tolerance"]
    assert "why" in tol and 0 < tol["mean_abs_nats"] < tol["max_abs_nats"]
    for word in ("control", "three passes", "norm between passes",
                 "post-sublayer norms"):
        assert word in tol["why"], word
    # the traffic file holds exactly ISSUE 63's parameters
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["ramp_s"], mix["plan_requests"],
            mix["shared_prefix"], mix["trace_seconds"]) == (
        "closed_loop", 32, 20, 1024, None, 3)
    assert mix["clients"] == 2 * f["max_batch_slots"]
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.5, "min": 32, "max": 320}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert mix["sampling"] == {
        "top_k": 1, "use_eod_token_for_early_termination": False}
    assert mix["probe_lengths"] == [150, 230]
    assert all(n % f["prefill_chunk"] for n in mix["probe_lengths"])
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] + 32 \
        <= f["engine_max_seq"]
    import glob

    seeds = [json.load(open(p)).get("draw_seed") for p in glob.glob(
        os.path.join(cell.bench_dir, "traffic", "*.json"))]
    assert seeds.count(mix["draw_seed"]) == 1           # of its own


def test_the_preset_is_the_configuration_and_the_bytes_are_the_trees():
    import jax

    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.models import init_model_params

    cell = _cell()
    body = cell.config
    cfg = parse_args(cell.flags({"seed": 1}))
    m = cfg.model
    assert cfg.model_name == "ouro"
    assert (m.num_layers, m.hidden_size, m.ffn_hidden_size,
            m.num_attention_heads, m.num_attention_heads_kv, m.kv_channels,
            m.vocab_size, m.layernorm_epsilon, m.rope_theta,
            m.max_position_embeddings, m.loop_steps) == (
        body["num_hidden_layers"], body["hidden_size"],
        body["intermediate_size"], body["num_attention_heads"],
        body["num_key_value_heads"], body["head_dim"], body["vocab_size"],
        body["rms_norm_eps"], body["rope_theta"],
        body["max_position_embeddings"], body["total_ut_steps"])
    # the program reads the LAST pass's logits: the published threshold
    assert body["early_exit_threshold"] == 1
    assert not m.tie_embed_logits and m.post_sublayer_norms
    shapes = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    d = body["derived"]
    assert d["layer_params"] == d["layer_gemm_params"] + 4 * 2048 == 51388416
    by_hand = (48 * d["layer_params"] + d["embedding_and_head_params"]
               + d["final_norm_and_gate_params"])
    assert sum(a.size for a in jax.tree.leaves(shapes)) == by_hand \
        == d["total_params"] == 2667974657
    assert m.cache_layer_slots == d["cache_layer_slots"]
    # the pool: pages of 16 tokens at 1.5 MiB a token, 24 MiB a page
    assert d["page_bytes"] == 16 * d["kv_bytes_per_token"] == 24 << 20
    pages = body["flags"]["kv_pool_pages"]
    assert 16 * 17 < pages - 1        # the slots' commitment at admission
    assert (pages * d["page_bytes"] + 2 * d["total_params"]) < 0.85 * 16.9e9


def test_bytes_by_hand():
    model = _model()
    assert flops_ouro.passes(model) == 4
    assert flops_ouro.cache_layer_slots(model) == 192
    # 16 K/V heads of 128, K and V, bf16, 192 slots: 1.5 MiB a token
    assert flops_ouro.kv_bytes_per_token(model) == 1572864 \
        == model["kv_bytes_per_token"]
    assert flops_ouro.layer_gemm_params(model) == (
        2048 * 6144 + 2048 * 2048 + 2048 * 11264 + 5632 * 2048) \
        == model["layer_gemm_params"]
    # a tick streams every layer's projections once a pass: 19.7 GB
    assert flops_ouro.tick_gemm_bytes(model) == 4 * 48 * 51380224 * 2 \
        == model["tick_gemm_bytes"]
    assert flops_ouro.needed_keys([DECODE, FRESH], (0.0, 1.0), 64) == \
        (151 + 152 + 153) + sum(
            min(e, 200) for e in range(64, 264, 64)) / 3


# ---- the capture -------------------------------------------------------------

def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _tick_ops(t0):
    return " ".join([_ev(2, t0, 20), _ev(3, t0 + 20, 40), _ev(4, t0 + 60, 10),
                     _ev(5, t0 + 70, 5), _ev(6, t0 + 75, 10)])


PALLAS = 'custom-call(%q), custom_call_target=\\"tpu_custom_call\\"'
STARTS = ((100, 1), (220, 1), (340, 7), (460, 1))    # (start, module's id)
CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000 %s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
  event_metadata { key: 1 value { id: 1 name: "jit_tick(77)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_tick(78)" } }
  event_metadata { key: 2 value { id: 2 name: "%%paged_attention.1 = f32[8] %s" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.1 = f32[8] fusion(%%p.1), kind=kOutput" } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.2 = f32[8] fusion(%%p.2), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.3 = f32[8] fusion(%%p.3), kind=kLoop" } }
  event_metadata { key: 6 value { id: 6 name: "%%glu_stack_matmul.1 = f32[8] %s" } }
}
""" % (" ".join(_ev(mid, t, 100) for t, mid in STARTS),
       " ".join(_tick_ops(t) for t, _ in STARTS), PALLAS, PALLAS)

PASS = "jit(tick)/decode-fwd/while/body/loop_pass/while/body/closed_call/"
OP_NAMES = {
    "paged_attention.1": PASS + "attention/pallas_call",
    "fusion.1": PASS + "mlp/dot_general",
    "fusion.2": PASS + "rsqrt",
    "fusion.3": "jit(tick)/decode-fwd/while/body/loop_norm_gate/dot_general",
    "glu_stack_matmul.1": PASS + ("mlp/jit(glu_stack_matmul)/glu_stack_matmul/"
                                  "pallas_call"),
}
DECODE = {"n_prompt": 150, "token_t": [-0.5, 0.1, 0.2, 0.3, 5.0],
          "sent_t": -9.0, "status": 200, "error": None}
FRESH = {"n_prompt": 200, "token_t": [2.0], "sent_t": -1.0, "status": 200,
         "error": None}


# ---- the programs' tables, as the profiler embeds them -----------------------

def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """(field number, an int or bytes or str) pairs on the protobuf wire."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _instruction(name, opcode, op_name="", calls=()):
    return _msg((1, name), (2, opcode), (7, _msg((2, op_name))),
                *((38, c) for c in calls))


def _program(fusions, scope="loop_pass"):
    """An HloProto: a fused computation a fusion (id 10 + i) holding
    ``body`` opcodes, and the entry computation calling them; the passes'
    scope under the name ``scope``."""
    bodies = [_msg((1, f"fused.{i}"), (5, 10 + i),
                   *((2, _instruction(f"b.{i}.{j}", opcode))
                     for j, opcode in enumerate(body)))
              for i, (_, _, body) in enumerate(fusions)]
    entry = _msg((1, "main"), (5, 1), *(
        (2, _instruction(name, "fusion",
                         op_name.replace("loop_pass", scope), [10 + i]))
        for i, (name, op_name, _) in enumerate(fusions)), *(
        (2, _instruction(name, "custom-call",
                         OP_NAMES[name].replace("loop_pass", scope)))
        for name in ("paged_attention.1", "glu_stack_matmul.1")))
    return _msg((1, _msg((1, "jit_tick"), *((3, c) for c in bodies),
                         (3, entry))))


GATE = "jit(tick)/decode-fwd/while/body/loop_norm_gate/dot_general"
PROGRAMS = {
    "jit_tick(77)": [("fusion.1", PASS + "mlp/dot_general",
                      ["parameter", "convolution", "convert"]),
                     ("fusion.2", PASS + "rsqrt", ["multiply", "rsqrt"]),
                     ("fusion.3", GATE, ["convolution"])],
    "jit_tick(78)": [("fusion.1", PASS + "attention/convert_element_type",
                      ["copy"]),
                     ("fusion.2", PASS + "attention/squeeze",
                      ["convolution", "bitcast"]),
                     ("fusion.3", GATE, ["convolution"])],
}


def _xplane(tmp_path, scope="loop_pass"):
    metadata = _msg((2, hlo_modules.METADATA_PLANE), *(
        (4, _msg((1, i), (2, _msg((1, i), (2, name), (5, _msg(
            (1, 9), (6, _program(fusions, scope))))))))
        for i, (name, fusions) in enumerate(PROGRAMS.items(), 1)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, _msg((2, "/device:TPU:0"))), (1, metadata)))
    return str(path)


def _profile(text):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return ProfileData.from_text_proto(text)


def _run(op_names, samples=(), span=(0.0, 1.0), capture=CAPTURE, path=""):
    reduced = trace.reduce_profile(_profile(capture), op_names)
    reduced.path = path
    cell = types.SimpleNamespace(model=_model(), traffic={})
    return types.SimpleNamespace(
        trace=reduced, peaks=peaks.peaks_for("TPU v5 lite"), cell=cell,
        chips=1, counters={}, trace_host=span, all_samples=list(samples),
        samples=list(samples), t_open=0.0, t_close=1.0,
        engine={"prefill_chunk": 64, "max_slots": 16})


def test_the_programs_tables_are_read_a_program(tmp_path):
    tables = hlo_modules.read(_xplane(tmp_path))
    assert set(tables) == set(PROGRAMS)
    products = {name: sorted(i for i, ins in table.items()
                             if ins.product and ins.opcode == "fusion")
                for name, table in tables.items()}
    # a fusion IS a product by its body, whatever its root is named
    assert products == {"jit_tick(77)": ["fusion.1", "fusion.3"],
                        "jit_tick(78)": ["fusion.2", "fusion.3"]}
    assert tables["jit_tick(78)"]["fusion.2"].op_name.endswith("/squeeze")
    assert tables["jit_tick(77)"]["paged_attention.1"].opcode == "custom-call"
    # an op is looked up in the program it ran in
    run = _run(OP_NAMES)
    ops = hlo_modules.Attributed(run.trace, tables)
    named = [(ops.run_of(o)[2], ops.instruction(o).product)
             for o in ops.ops if o.name == "fusion.1"]
    assert named == [("jit_tick(77)", True), ("jit_tick(77)", True),
                     ("jit_tick(78)", False), ("jit_tick(77)", True)]


def test_gemm_roofline_reads_the_products_of_each_program(tmp_path, capsys):
    # TWO whole ticks, one a program.  Products under loop_pass: in 77 the
    # projection fusion.1 (40 us) and the GLU kernel (10); in 78 fusion.2
    # (10 us, a `squeeze` by its root) and the GLU kernel (10): 70 us.  The
    # glue: 77's norm (10) and 78's copy (40).  The gate's product is not
    # under loop_pass, and the paged kernel is neither
    got = _reader("loop_gemm_roofline.ouro").reduce(
        _run(OP_NAMES, path=_xplane(tmp_path)))
    need = 2 * 4 * 48 * 51380224 * 2
    assert got == pytest.approx(100.0 * need / 819e9 / 70e-6)
    said = capsys.readouterr().out
    assert "2 whole ticks" in said
    assert "0.07 ms in the passes' matrix products" in said
    assert "0.05 ms in the glue" in said


def test_paged_roofline_and_share_read_the_kernel(capsys):
    got = _reader("paged_attn_roofline.ouro").reduce(
        _run(OP_NAMES, [DECODE, FRESH]))
    keys = (151 + 152 + 153) + sum(
        min(e, 200) for e in range(64, 264, 64)) / 3
    assert got == pytest.approx(100.0 * keys * 1572864 / 819e9 / 80e-6)
    assert "over 192 layer slots" in capsys.readouterr().out
    # 4 x 20 us of kernel in 4 x 85 us busy
    assert _reader("paged_attn_share.ouro").reduce(_run(OP_NAMES)) == \
        pytest.approx(100.0 * 80 / 340)


def test_passes_per_token_counts_what_the_device_ran(tmp_path):
    # one kernel call a whole tick: a stack of ONE layer ran one pass; of
    # two, half of one (a layer or a pass left out reads low)
    run = _run(OP_NAMES, path=_xplane(tmp_path))
    for layers, want in ((1, 1.0), (2, 0.5)):
        run.cell = types.SimpleNamespace(
            model={**_model(), "num_hidden_layers": layers})
        assert _reader("loop_passes_per_token.ouro").reduce(run) == want
    # four calls a tick of a one-layer stack: four passes
    four = CAPTURE.replace(_ev(2, 220, 20), " ".join(
        _ev(2, 220 + 5 * i, 5) for i in range(4))).replace(
        _ev(2, 340, 20), " ".join(_ev(2, 340 + 5 * i, 5) for i in range(4)))
    run = _run(OP_NAMES, capture=four, path=_xplane(tmp_path))
    run.cell = types.SimpleNamespace(
        model={**_model(), "num_hidden_layers": 1})
    assert _reader("loop_passes_per_token.ouro").reduce(run) == 4.0


def test_readers_report_nothing_without_their_source(tmp_path):
    bare = types.SimpleNamespace(
        trace=None, peaks=None, counters={}, trace_host=None, all_samples=[],
        samples=[], t_open=0.0, t_close=1.0, engine={},
        cell=types.SimpleNamespace(model=_model()))
    for name in READERS:
        assert _reader(name).reduce(bare) is None, name
    # a capture whose programs lack the scope (the parent's), a trace
    # without its programs' tables, a capture without the kernel, and a cell
    # of another model
    for path in (_xplane(tmp_path, scope="layers"), ""):
        plain = _run({}, [DECODE], path=path)
        for name in ("loop_passes_per_token.ouro", "loop_gemm_roofline.ouro"):
            assert _reader(name).reduce(plain) is None, name
    unnamed = CAPTURE.replace("paged_attention", "mamba_sweep")
    for name in ("paged_attn_roofline.ouro", "paged_attn_share.ouro"):
        assert _reader(name).reduce(
            _run({}, [DECODE], capture=unnamed)) is None, name
    other = _run(OP_NAMES, [DECODE], path=_xplane(tmp_path))
    other.cell = types.SimpleNamespace(model={"hidden_size": 64})
    for name in READERS:
        assert _reader(name).reduce(other) is None, name


# ---- the cell, rehearsed ---------------------------------------------------------

def test_the_cell_rehearses_correct_through_every_pass():
    """``run.py --rehearsal 1`` without its look for a chip: tiny widths,
    four slots, probes of 26 and 41 tokens: the probes hit the prefix cache,
    the reference's four passes agree at the emitted positions, and the
    exit masses are on /metrics."""
    from benchmark.lib import harness, serving

    cell = _cell()
    args = types.SimpleNamespace(seed=2147485063, seconds=4.0, trace=0,
                                 rehearsal=1, rate=None)
    run = serving.run(cell, args, harness.Clock(harness.Clock.now()))
    c = run.checks
    assert c["probe_prefix_hit_tokens"] > 0
    assert c["reference_ok"] and c["reference_tokens"] == 128
    assert c["reference_max_abs_diff"] < 2e-3          # float32 on the CPU
    assert run.correct and run.attempted > 0 and run.failed == 0
    assert run.engine["max_slots"] == 4 and run.engine["page_size"] == 8
    assert _reader("paged_rows_per_walk.batch").reduce(run) >= 1.0
    assert 0 < _reader("paged_fetch_share.batch").reduce(run) <= 100.0
    assert _reader("slot_occupancy.batch").reduce(run) > 0
    assert _reader("pool_dry_tick_share.batch").reduce(run) is not None
    masses = [run.counters[
        f'mlt_engine_loop_exit_mass_total{{step="{t}"}}'] for t in (1, 2, 3, 4)]
    assert min(masses) > 0
    assert "mlt_engine_loop_layer_passes_total" not in run.counters
    assert sum(masses) == pytest.approx(
        run.counters["mlt_engine_ticked_tokens_total"], rel=0.05)
