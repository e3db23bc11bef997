"""bench.py evidence contract (VERDICT round-2 item 1).

Off-TPU the headline fields must report 0 (a CPU step time over a nominal
peak is not an MFU measurement) with the run riding under ``cpu_sanity``;
host-cost budgets stamp ``error`` on a line that drifts.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_metric_name_carries_seq():
    assert bench.metric_name(1024) == bench.METRIC
    assert "seq32768" in bench.metric_name(32768)


def test_cpu_contract_zeroes_headline():
    line = bench.cpu_contract_line({
        "metric": bench.METRIC, "value": 6.75, "unit": "%MFU",
        "vs_baseline": 0.577, "backend": "cpu", "loss": 7.3,
        "tokens_per_sec": 111.0})
    assert line["value"] == 0.0 and line["vs_baseline"] == 0.0
    assert line["cpu_sanity"]["tokens_per_sec"] == 111.0
    assert "value" not in line["cpu_sanity"]
    # unit preserved for non-default metrics (moe_bench)
    moe = bench.cpu_contract_line({"metric": "m", "value": 5.0,
                                   "unit": "%MFU(active)", "backend": "cpu"})
    assert moe["unit"] == "%MFU(active)" and "vs_baseline" not in moe


def test_decode_bench_cpu_contract():
    """The decode tool reuses bench.py's off-TPU contract: headline 0,
    run rides under cpu_sanity, tagged evidence file when on TPU."""
    line = bench.cpu_contract_line({
        "metric": "decode_tok_s_llama470m_b8_p128_g128_1chip",
        "value": 1234.5, "unit": "tok/s", "backend": "cpu",
        "rows": [{"batch": 8, "decode_tok_s": 1234.5}]}, tag="decode")
    assert line["value"] == 0.0 and line["unit"] == "tok/s"
    assert line["cpu_sanity"]["rows"][0]["decode_tok_s"] == 1234.5


def test_engine_decode_bench_cpu_contract():
    """bench_decode.py (ISSUE 1) reuses bench.py's off-TPU contract:
    headline 0, the occupancy sweep + speedup ride under cpu_sanity, TPU
    evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "engine_decode_tok_s_llama470m_c8_1chip",
        "value": 2285.1, "unit": "tok/s", "backend": "cpu",
        "speedup_vs_sequential": 5.48,
        "rows": [{"concurrency": 8, "engine_tok_s": 2285.1,
                  "tick_ms": 3.5, "speedup_vs_sequential": 5.48}],
    }, tag="engine_decode")
    assert line["value"] == 0.0 and line["unit"] == "tok/s"
    assert line["cpu_sanity"]["speedup_vs_sequential"] == 5.48
    assert line["cpu_sanity"]["rows"][0]["tick_ms"] == 3.5


def test_prefix_bench_cpu_contract():
    """bench_decode.py --mode shared_prefix (ISSUE 5) reuses bench.py's
    off-TPU contract: headline 0, the cache-on/off comparison (prefill
    tokens, TTFT, hit rate) rides under cpu_sanity, TPU evidence goes to
    its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "engine_prefix_prefill_reduction_llama470m_c8_1chip",
        "value": 7.0, "unit": "x", "backend": "cpu",
        "ttft_mean_speedup": 1.7, "hit_rate": 0.92,
        "rows": [{"concurrency": 8, "prefill_token_reduction": 7.0,
                  "reduction_ok": True,
                  "cache_on": {"prefill_tokens_computed": 128},
                  "cache_off": {"prefill_tokens_computed": 896}}],
    }, tag="engine_decode_prefix")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["ttft_mean_speedup"] == 1.7
    assert line["cpu_sanity"]["rows"][0]["reduction_ok"] is True


def test_slo_bench_cpu_contract():
    """bench_decode.py --mode slo (ISSUE 7) reuses bench.py's off-TPU
    contract: headline 0, the per-policy TTFT/deadline-miss/preemption
    comparison rides under cpu_sanity WITH the host-cost budget fields
    populated, TPU evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "engine_slo_hi_p99_ttft_speedup_llama470m_1chip",
        "value": 3.1, "unit": "x", "backend": "cpu",
        "speedup_ok": True,
        "hi_deadline_miss_rate": {"fcfs": 1.0, "slo": 0.0},
        "preemptions": {"fcfs": 0, "slo": 2},
        "compile_time_s": 2.7, "step_time_s": 0.002,
        "rows": [{"policy": "fcfs", "hi": {"ttft_p99_ms": 359.0}},
                 {"policy": "slo", "hi": {"ttft_p99_ms": 115.0}}],
    }, tag="engine_decode_slo")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["speedup_ok"] is True
    assert line["cpu_sanity"]["hi_deadline_miss_rate"]["slo"] == 0.0
    assert line["cpu_sanity"]["preemptions"]["slo"] == 2
    # budget fields populated and within caps (no error stamp)
    assert line["budgets"]["compile_time_s"]["value"] == 2.7
    assert line["budgets"]["step_time_s"]["budget"] == 120.0
    assert "error" not in line


def test_committed_slo_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_slo_cpu_sanity.json)
    satisfies the contract: headline 0 off-TPU, >= 2x hi-priority p99
    TTFT for slo vs fcfs, miss rates + preemptions present, budgets
    populated without violations."""
    import json
    from pathlib import Path

    path = Path(__file__).parent.parent / "BENCH_decode_slo_cpu_sanity.json"
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["speedup_ok"] is True
    by = {r["policy"]: r for r in sanity["rows"]}
    assert set(by) == {"fcfs", "priority", "slo"}
    assert (by["fcfs"]["hi"]["ttft_p99_ms"]
            >= 2.0 * by["slo"]["hi"]["ttft_p99_ms"])
    assert by["slo"]["preemptions"] >= 1
    for row in by.values():
        assert {"ttft_p50_ms", "ttft_p99_ms",
                "deadline_miss_rate"} <= set(row["hi"])
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


def test_spec_bench_cpu_contract():
    """bench_decode.py --mode spec (ISSUE 9) reuses the off-TPU contract:
    headline 0, the spec-on/off comparison + acceptance rate ride under
    cpu_sanity with the budget fields populated."""
    line = bench.cpu_contract_line({
        "metric": "engine_spec_decode_speedup_llama470m_c1_1chip",
        "value": 1.7, "unit": "x", "backend": "cpu",
        "speedup_ok": True, "acceptance_rate": 1.0, "spec_k": 4,
        "compile_time_s": 5.0, "step_time_s": 0.013,
        "rows": [{"concurrency": 1, "speedup": 1.7,
                  "on": {"decode_tok_s": 350.0, "acceptance_rate": 1.0},
                  "off": {"decode_tok_s": 206.0}}],
    }, tag="engine_decode_spec")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["speedup_ok"] is True
    assert line["cpu_sanity"]["acceptance_rate"] == 1.0
    assert line["budgets"]["compile_time_s"]["value"] == 5.0
    assert "error" not in line


def test_committed_spec_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_spec_cpu_sanity.json)
    satisfies the contract: headline 0 off-TPU, >= 1.3x decode tok/s at
    concurrency 1 with the acceptance rate alongside, budgets populated,
    and no ``error`` stamped."""
    import json as _json
    from pathlib import Path

    path = Path(__file__).parent.parent / "BENCH_decode_spec_cpu_sanity.json"
    rec = _json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["speedup_ok"] is True
    assert sanity["acceptance_rate"] is not None
    by_c = {r["concurrency"]: r for r in sanity["rows"]}
    assert by_c[1]["speedup"] >= 1.3
    for row in by_c.values():
        assert {"decode_tok_s", "latency_p50_ms",
                "latency_p99_ms"} <= set(row["on"])
        assert "acceptance_rate" in row["on"]
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


def test_router_bench_cpu_contract():
    """bench_decode.py --mode router (ISSUE 10) reuses the off-TPU
    contract: headline 0, the prefix_affinity-vs-round_robin comparison +
    failover record ride under cpu_sanity with the budget fields
    populated, TPU evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "router_prefix_affinity_ttft_speedup_llama470m_2rep_1chip",
        "value": 1.3, "unit": "x", "backend": "cpu",
        "speedup_ok": True, "fleet_hit_rate_gain": 0.23,
        "failover": {"killed": "http://127.0.0.1:1", "requests": 12,
                     "dropped": 0, "failovers": 2,
                     "killed_state": "ejected", "ok": True},
        "compile_time_s": 40.0, "step_time_s": 0.02,
        "rows": [{"policy": "round_robin", "fleet_hit_rate": 0.75,
                  "ttft_mean_ms": 369.0},
                 {"policy": "prefix_affinity", "fleet_hit_rate": 0.98,
                  "ttft_mean_ms": 328.0}],
    }, tag="engine_decode_router")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["speedup_ok"] is True
    assert line["cpu_sanity"]["failover"]["dropped"] == 0
    assert line["budgets"]["compile_time_s"]["value"] == 40.0
    assert "error" not in line


def test_committed_router_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_router_cpu_sanity
    .json) satisfies the acceptance bar: headline 0 off-TPU,
    prefix_affinity beats round_robin on BOTH fleet prefix-hit rate and
    mean TTFT, the mid-run kill dropped nothing and ejected the dead
    replica, budgets populated without violations."""
    from pathlib import Path

    path = (Path(__file__).parent.parent
            / "BENCH_decode_router_cpu_sanity.json")
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["speedup_ok"] is True
    by = {r["policy"]: r for r in sanity["rows"]}
    assert set(by) == {"round_robin", "prefix_affinity"}
    aff, rr = by["prefix_affinity"], by["round_robin"]
    assert aff["fleet_hit_rate"] > rr["fleet_hit_rate"]
    assert aff["ttft_mean_ms"] < rr["ttft_mean_ms"]
    assert aff["prefill_tokens_computed"] < rr["prefill_tokens_computed"]
    fo = sanity["failover"]
    assert fo["dropped"] == 0 and fo["ok"] is True
    assert fo["failovers"] >= 1
    assert fo["killed_state"] in ("suspect", "ejected")
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


# ---------------------------------------------------------------------------
# ISSUE 13: quantized-KV capacity bench
# ---------------------------------------------------------------------------


def test_capacity_bench_cpu_contract():
    """bench_decode.py --mode capacity (ISSUE 13) reuses the off-TPU
    contract: headline 0, the fixed-byte-budget int8-vs-bf16 comparison
    rides under cpu_sanity with budget fields populated, TPU evidence
    goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "engine_kv_capacity_slot_ratio_llama470m_1chip",
        "value": 2.3, "unit": "x", "backend": "cpu",
        "capacity_ok": True, "greedy_match": True, "slot_ratio": 2.3,
        "hit_rate_bf16": 0.44, "hit_rate_int8": 0.89,
        "compile_time_s": 3.0, "step_time_s": 0.05,
        "rows": [{"kv_dtype": "bf16", "peak_concurrent_slots": 3},
                 {"kv_dtype": "int8", "peak_concurrent_slots": 7}],
    }, tag="engine_decode_capacity")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["capacity_ok"] is True
    assert line["budgets"]["compile_time_s"]["value"] == 3.0
    assert line["budgets"]["step_time_s"]["budget"] == 120.0
    assert "error" not in line


def test_committed_capacity_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_capacity_cpu_
    sanity.json) satisfies the acceptance bar: headline 0 off-TPU, the
    int8 arm sustains >= 2x the bf16 arm's peak concurrent slots at the
    SAME pool byte budget, the prefix hit rate is no worse, greedy
    tokens matched on the sanity horizon, and budgets populated without
    violations."""
    from pathlib import Path

    path = (Path(__file__).parent.parent
            / "BENCH_decode_capacity_cpu_sanity.json")
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["capacity_ok"] is True
    assert sanity["greedy_match"] is True
    assert sanity["slot_ratio"] >= 2.0
    by = {r["kv_dtype"]: r for r in sanity["rows"]
          if "peak_concurrent_slots" in r}
    assert set(by) == {"bf16", "int8"}
    # SAME byte budget on both arms — the whole point of the bench
    assert (by["int8"]["pool_budget_bytes"]
            == by["bf16"]["pool_budget_bytes"])
    assert (by["int8"]["peak_concurrent_slots"]
            >= 2 * by["bf16"]["peak_concurrent_slots"])
    # int8 value bytes actually fit the budget, scale overhead included
    assert (by["int8"]["kv_pool_bytes"] + by["int8"]["kv_scale_bytes"]
            <= by["int8"]["pool_budget_bytes"])
    assert sanity["hit_rate_int8"] >= sanity["hit_rate_bf16"]
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


def test_trace_cost_budget_on_observability_line():
    """ROADMAP item 4 leftover: the observability evidence line carries
    tracer-cost budget verdicts — within limits it annotates, a tracer
    regression stamps ``error``."""
    ok = bench.cpu_contract_line({
        "metric": "train_observability_overhead_llama470m_1chip",
        "value": 1.9, "unit": "steps/s", "backend": "cpu",
        "overhead_pct": 1.2, "instrument_cost_us_per_step": 110.0,
    }, tag="observability")
    assert ok["budgets"]["instrument_cost_us_per_step"]["budget"] == 2000.0
    assert ok["budgets"]["overhead_pct"]["budget"] == 10.0
    assert "error" not in ok

    drifted = bench.cpu_contract_line({
        "metric": "train_observability_overhead_llama470m_1chip",
        "value": 1.9, "unit": "steps/s", "backend": "cpu",
        "overhead_pct": 1.2, "instrument_cost_us_per_step": 5000.0,
    }, tag="observability")
    assert "instrument_cost_us_per_step" in drifted["error"]


def test_resilience_smoke_cpu_contract():
    """Off-TPU the smoke reports headline 0 under the bench contract, with
    the chaos measurements riding in cpu_sanity; TPU evidence goes to its
    own tagged file and never clobbers the headline record."""
    line = bench.cpu_contract_line({
        "metric": "resilience_chaos_goodput_1chip",
        "value": 87.5, "unit": "%goodput", "backend": "cpu",
        "passed": True,
        "chaos": {"bitwise_identical": True, "attempt_classes":
                  ["signal", "clean"]},
    }, tag="resilience")
    assert line["value"] == 0.0 and line["unit"] == "%goodput"
    assert line["cpu_sanity"]["chaos"]["bitwise_identical"] is True


def test_observability_bench_cpu_contract():
    """Off-TPU the observability bench reports headline 0 under the bench
    contract with the off/on comparison riding in cpu_sanity; TPU
    evidence goes to its own tagged file and never clobbers the
    headline."""
    line = bench.cpu_contract_line({
        "metric": "train_loop_observed_steps_s_1chip",
        "value": 6.7, "unit": "steps/s", "backend": "cpu",
        "baseline_steps_per_sec": 6.9, "overhead_pct": 1.9,
        "pair_ratios": [0.98, 0.99, 1.0, 1.01], "rounds": 4,
        "passed": True, "loss_bitwise_identical": True,
        "instrument_cost_us_per_step": 99.7,
    }, tag="observability")
    assert line["value"] == 0.0 and line["unit"] == "steps/s"
    assert line["cpu_sanity"]["overhead_pct"] == 1.9
    assert line["cpu_sanity"]["loss_bitwise_identical"] is True


def test_e2e_470m_contract_line():
    """tools/e2e_470m.py off-TPU: headline 0."""
    from tools.e2e_470m import cpu_contract_record

    line = cpu_contract_record()  # the record main() prints off-TPU
    assert line["value"] == 0 and line["vs_baseline"] == 0


def test_e2e_staged_helpers(tmp_path):
    """parse_train_loss survives format drift (ADVICE r4 #3); done_iters
    reads the tracker and is robust to absence/garbage."""
    from tools.e2e_470m import done_iters, parse_train_loss

    out = ("iteration   50/ 100 | lm loss: 7.234052 | lr: 1e-4 |\n"
           "noise\n"
           "iteration  100/ 100 | lm loss: 5.299069 | lr: 9e-5 |\n")
    assert parse_train_loss(out) == 5.299069
    assert parse_train_loss("iteration 1 | lm loss: garbage | x") is None
    assert parse_train_loss("") is None

    assert done_iters(str(tmp_path)) == 0  # no tracker
    (tmp_path / "latest_checkpointed_iteration.txt").write_text("250\n")
    assert done_iters(str(tmp_path)) == 250
    (tmp_path / "latest_checkpointed_iteration.txt").write_text("release")
    assert done_iters(str(tmp_path)) == 0
    (tmp_path / "latest_checkpointed_iteration.txt").write_text("junk")
    assert done_iters(str(tmp_path)) == 0


# ---------------------------------------------------------------------------
# ISSUE 6: host-cost budgets + the tp mesh bench
# ---------------------------------------------------------------------------


def test_budgets_annotate_within_limits():
    """A contract line whose compile/step/dispatch costs sit inside the
    budgets gains the budgets block and NO error."""
    line = bench.cpu_contract_line({
        "metric": "m", "value": 1.0, "unit": "x", "backend": "cpu",
        "compile_time_s": 40.0, "step_time_s": 20.0,
        "step_time_dispatch_s": 0.1,
    })
    assert "error" not in line
    assert line["budgets"]["compile_time_s"]["value"] == 40.0
    assert line["budgets"]["compile_time_s"]["budget"] == 180.0
    assert line["budgets"]["step_time_s"]["budget"] == 120.0


def test_budgets_fail_loudly_on_drift():
    """The BENCH_r02-r05 drift shape (compile 38s -> 100s -> beyond) must
    flip the line to an error — no more silent upward creep across
    evidence files."""
    line = bench.cpu_contract_line({
        "metric": "m", "value": 1.0, "unit": "x", "backend": "cpu",
        "compile_time_s": 500.0, "step_time_s": 20.0,
    })
    assert "budget exceeded" in line["error"]
    assert any("compile_time_s" in v for v in line["budget_exceeded"])


def test_budgets_env_override(monkeypatch):
    monkeypatch.setenv("MLT_BENCH_BUDGET_STEP_TIME_S", "1.0")
    line = bench.apply_budgets({"cpu_sanity": {"step_time_s": 2.0},
                                "metric": "m"})
    assert "error" in line and "step_time_s" in line["error"]


def test_budgets_skip_missing_fields():
    """Benches that don't report a field aren't judged on it."""
    line = bench.apply_budgets({"cpu_sanity": {"hit_rate": 0.9},
                                "metric": "m"})
    assert "error" not in line and "budgets" not in line


def test_tp_bench_cpu_contract():
    """bench_tp.py rides the same off-TPU contract: headline 0, per-layout
    mechanism checks under cpu_sanity, budget fields populated from the
    largest layout, tagged TPU evidence file."""
    line = bench.cpu_contract_line({
        "metric": "tp_mesh_train_steps_s", "value": 25.9, "unit": "steps/s",
        "backend": "cpu",
        "layouts": [
            {"tp": 1, "all_reduce_count": 0, "loss": 6.1},
            {"tp": 4, "all_reduce_count": 67, "loss": 6.1},
        ],
        "loss_parity_vs_tp1": {"tp4_loss_delta": 0.0},
        "engine_tokens_match_tp1": True,
        "step_time_s": 0.04, "step_time_dispatch_s": 0.04,
        "compile_time_s": 2.0,
    }, tag="tp")
    assert line["value"] == 0.0
    assert line["cpu_sanity"]["layouts"][1]["all_reduce_count"] > 0
    assert line["budgets"]["compile_time_s"]["value"] == 2.0
    assert "error" not in line


def test_tp_bench_committed_cpu_evidence():
    """The CPU-sanity evidence JSON is committed with the budget fields
    populated (ISSUE 6 acceptance)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_tp_cpu_sanity.json")
    with open(path) as f:
        line = json.load(f)
    assert line["metric"] == "tp_mesh_train_steps_s"
    assert line["value"] == 0.0  # CPU headline contract
    assert "error" not in line
    for field in ("compile_time_s", "step_time_s", "step_time_dispatch_s"):
        assert field in line["budgets"], field
    sanity = line["cpu_sanity"]
    by_tp = {r["tp"]: r for r in sanity["layouts"] if "skipped" not in r}
    assert by_tp[4]["all_reduce_count"] > 0
    assert by_tp[1]["all_reduce_count"] == 0
    assert sanity["loss_parity_vs_tp1"]["tp4_loss_delta"] <= 1e-4
    assert sanity["engine_tokens_match_tp1"] is True


def test_tp_bench_committed_overlap_evidence():
    """ISSUE 15 acceptance: the committed bench_tp evidence carries the
    overlap arm with the mechanism MACHINE-asserted — ppermute chain +
    forward-tp{N}-overlap scope in the compiled HLO, loss parity within
    rel 1e-4 of the overlap-off row (chunked-GEMM reassociation:
    tolerance, not bitwise), engine greedy tokens identical."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_tp_cpu_sanity.json")
    with open(path) as f:
        line = json.load(f)
    arm = line["cpu_sanity"]["overlap"]
    assert arm["mechanism_ok"] is True
    rows = [r for r in arm["layouts"] if "skipped" not in r]
    assert rows, "overlap arm has no measured layouts"
    for r in rows:
        assert r["tp_overlap"] == "ring"
        assert r["overlap_scope_in_hlo"] is True
        assert r["ppermute_chain"] is True
        assert r["loss_rel_vs_off"] <= 1e-4
        assert r["engine_tokens_match_off"] is True
        # the ring re-associates but must not lose the tp collectives'
        # semantics: the layout still reports tp-sharded params
        assert r["tp_sharded_leaves"] > 0


def test_tp_bench_overlap_arm_shape():
    """run_overlap_arm contract on synthetic rows: mechanism_ok goes
    false when any check fails, and tp=1 rows are never ring-armed."""
    import bench_tp

    base = [{"tp": 1, "step_time_s": 1.0, "loss": 6.0,
             "collective_permute_count": 0}]
    arm = bench_tp.run_overlap_arm([1], 1, 64, 2, 64, 0, base, [])
    assert arm["layouts"] == [] and arm["mechanism_ok"] is True


# ---------------------------------------------------------------------------
# ISSUE 12: bench-trajectory drift detector (tools/bench_drift.py)
# ---------------------------------------------------------------------------


def test_bench_drift_computation_synthetic():
    """Per-metric drift math: ratio of newest to earliest committed
    round, direction-aware thresholds, rounds without the metric
    skipped."""
    from tools.bench_drift import compute_drift

    rows = [
        (2, "BENCH_r02.json", {"step_time_s": 10.0, "compile_time_s": 40.0,
                               "tokens_per_sec": 100.0}),
        (3, "BENCH_r03.json", {"step_time_s": 11.0}),
        (5, "BENCH_r05.json", {"step_time_s": 12.0, "compile_time_s": 44.0,
                               "tokens_per_sec": 90.0}),
    ]
    res = compute_drift(rows)
    assert res["verdict"] == "ok"
    m = res["metrics"]["step_time_s"]
    assert m["rounds"] == 3 and m["ratio"] == 1.2 and not m["exceeded"]
    assert res["metrics"]["compile_time_s"]["rounds"] == 2
    # now push step time past the ceiling
    rows.append((6, "BENCH_r06.json", {"step_time_s": 31.0}))
    res = compute_drift(rows)
    assert res["verdict"] == "drift"
    assert res["metrics"]["step_time_s"]["exceeded"] is True
    assert res["metrics"]["tokens_per_sec"]["exceeded"] is False
    # thresholds are configurable
    res = compute_drift(rows, {"step_time_s": 4.0})
    assert res["metrics"]["step_time_s"]["exceeded"] is False


def test_bench_drift_flags_committed_trajectory():
    """ROADMAP item 3 CLOSED (ISSUE 15): the r02->r05 "drift" was
    root-caused as host contention, not code — the round-5 record
    (step 52.2s / compile 100.4s) was measured while the staged 470M
    e2e jobs shared the single-core host (both metrics inflated by the
    same ~2.1x, the signature of CPU-time division), and re-measuring
    the EXACT r05 tree on an idle host gives 24.4s/47.6s, matching the
    r04 tree (23.6s/47.8s) and HEAD.  BENCH_r06.json is the clean
    re-measurement (its ``note`` carries the bisect evidence).  This
    test now pins the FIX: the refreshed trajectory must stay within
    the drift thresholds — any future round that trips them is a real
    regression to bisect, not carried debt."""
    from tools.bench_drift import compute_drift, load_trajectory

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = load_trajectory(repo)
    assert len(rows) >= 5, "committed BENCH_r* trajectory went missing"
    assert rows[-1][1] == "BENCH_r06.json", (
        "the root-cause refresh round went missing — newest round is "
        f"{rows[-1][1]}")
    res = compute_drift(rows)
    assert res["verdict"] == "ok", res
    for field in ("step_time_s", "compile_time_s", "tokens_per_sec"):
        assert res["metrics"][field]["exceeded"] is False, res["metrics"]
    # the contaminated r05 point stays committed (history is honest);
    # only the newest-vs-earliest ratio gates
    assert res["metrics"]["step_time_s"]["ratio"] < 1.5
    assert res["metrics"]["compile_time_s"]["ratio"] < 1.5


# ---------------------------------------------------------------------------
# ISSUE 14: two-pass graftcheck sweep wall-time + changed-only warm cost
# ---------------------------------------------------------------------------


def test_graftcheck_two_pass_sweep_walltime(tmp_path):
    """The whole-repo two-pass sweep (per-file rules + lock-order +
    wire-contract analyzers) stays under 45 s wall — the budget that
    keeps it viable as a tier-1 gate.  The warm
    --changed-only path (pass-1 scoped to changed files, pass-2 facts
    from the cache) must be a small fraction of that: it is the local
    pre-commit loop."""
    from tools.graftcheck import core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    targets = [os.path.join(repo, t)
               for t in ("megatron_llm_tpu", "tools", "tasks", "tests")]
    cache = str(tmp_path / "factcache.json")
    full = core.run(targets, root=repo, fact_cache_path=cache)
    assert full.files > 150
    assert full.seconds < 45, f"full sweep {full.seconds:.1f}s > 45s"
    warm = core.run(targets, root=repo, changed_files=[],
                    fact_cache_path=cache)
    assert warm.changed_only
    assert warm.seconds < max(5.0, full.seconds / 2), (
        f"warm changed-only run {warm.seconds:.1f}s — the fact cache "
        f"is not being hit")
    # the cached pass-2 still sees the whole project
    lo = warm.artifacts["lockorder"]
    assert ("ContinuousBatchingEngine._lock", "FlightRecorder._lock") \
        in {(e["from"], e["to"]) for e in lo["edges"]}


def test_graftcheck_lockorder_evidence_committed():
    """tools/graftcheck/lockorder.json rides the same reviewed-evidence
    contract as the BENCH files: present, schema-valid, cycle-free,
    with the engine→recorder edge the flight recorder's safety argument
    rests on.  (Equality with the freshly derived graph is pinned in
    tests/test_graftcheck.py::test_lockorder_committed_evidence.)"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "tools", "graftcheck", "lockorder.json")
    assert os.path.exists(path), "committed lock-graph evidence missing"
    with open(path) as f:
        doc = json.load(f)
    assert doc["graftcheck_lockorder"] == 1
    assert doc["cycles"] == []
    assert doc["order"], "committed graph must be acyclic + ordered"
    assert len(doc["nodes"]) >= 15
    assert ("ContinuousBatchingEngine._lock", "FlightRecorder._lock") \
        in {(e["from"], e["to"]) for e in doc["edges"]}
    for e in doc["edges"]:
        assert e["examples"], "every edge needs a source example site"


def test_streaming_bench_cpu_contract():
    """bench_decode.py --mode streaming (ISSUE 18) reuses the off-TPU
    contract: headline 0, the streamed-vs-buffered TTFT comparison and
    the admission-queue burst rows ride under cpu_sanity with budget
    fields populated, TPU evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric":
            "serving_stream_first_token_speedup_llama470m_c8_2rep_1chip",
        "value": 2.4, "unit": "x", "backend": "cpu",
        "first_token_speedup": 2.4, "stream_ok": True,
        "stamp_ratio": 1.1, "stamp_ok": True,
        "buffered_first_byte_is_total": True, "identity_ok": True,
        "baseline_dropped": 8, "admission_dropped": 0,
        "compile_time_s": 3.0, "step_time_s": 0.01,
        "rows": [{"arm": "streamed", "client_ttft_mean_ms": 55.0,
                  "replica_stamp_mean_ms": 50.0, "total_mean_ms": 170.0},
                 {"arm": "buffered", "client_ttft_mean_ms": 132.0,
                  "total_mean_ms": 135.0},
                 {"admission_queue": False, "requests": 12, "ok": 4,
                  "dropped": 8},
                 {"admission_queue": True, "requests": 12, "ok": 12,
                  "dropped": 0}],
    }, tag="engine_decode_streaming")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["stream_ok"] is True
    assert line["cpu_sanity"]["admission_dropped"] == 0
    assert line["budgets"]["compile_time_s"]["value"] == 3.0
    assert "error" not in line


def test_committed_streaming_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_streaming_cpu_
    sanity.json) satisfies the acceptance bar: headline 0 off-TPU, the
    streamed client's first byte lands within the stamp-honesty gate and
    strictly before the buffered client's (speedup >= 1), the streamed
    terminal body matched the buffered response byte-for-byte, the
    saturation burst 503'd without the admission queue and dropped
    nothing with it, budgets populated without violations."""
    from pathlib import Path

    path = (Path(__file__).parent.parent
            / "BENCH_decode_streaming_cpu_sanity.json")
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["stream_ok"] is True
    assert sanity["stamp_ok"] is True
    assert sanity["identity_ok"] is True
    assert sanity["buffered_first_byte_is_total"] is True
    assert sanity["first_token_speedup"] >= 1.0
    by_arm = {r["arm"]: r for r in sanity["rows"] if "arm" in r}
    assert set(by_arm) == {"streamed", "buffered"}
    # streaming delivers the first token earlier than the buffered
    # response delivers anything at all
    assert (by_arm["streamed"]["client_ttft_mean_ms"]
            < by_arm["buffered"]["client_ttft_mean_ms"])
    # every streamed response carried the replica's X-MLT-TTFT-S stamp
    assert by_arm["streamed"]["stamped"] == sanity["workload"]["concurrency"]
    bursts = {r["admission_queue"]: r for r in sanity["rows"]
              if "admission_queue" in r}
    assert set(bursts) == {False, True}
    assert bursts[False]["dropped"] > 0  # the burst genuinely saturates
    assert bursts[True]["dropped"] == 0
    assert bursts[True]["ok"] == bursts[True]["requests"]
    assert bursts[True]["admission_stats"]["overflows"] == 0
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


def test_disagg_bench_cpu_contract():
    """bench_decode.py --mode disagg (ISSUE 19) reuses the off-TPU
    contract: headline 0, the unified-vs-split fleet TPOT comparison and
    the per-arm/class rows ride under cpu_sanity with budget fields
    populated, TPU evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric":
            "serving_disagg_decode_p99_tpot_speedup_llama470m_2rep_1chip",
        "value": 1.4, "unit": "x", "backend": "cpu",
        "decode_tpot_p99_speedup": 1.4, "decode_tpot_mean_speedup": 1.3,
        "disagg_ok": True, "identity_ok": True,
        "handoffs": 7.0, "handoff_failures": 0.0,
        "long_ttft_mean_ms": {"unified": 2100.0, "split": 1800.0},
        "compile_time_s": 6.0, "step_time_s": 0.05,
        "rows": [{"arm": "unified+unified", "class": "short",
                  "requests": 24, "tpot_p99_ms": 104.0},
                 {"arm": "prefill+decode", "class": "short",
                  "requests": 24, "tpot_p99_ms": 76.0}],
    }, tag="engine_decode_disagg")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["disagg_ok"] is True
    assert line["cpu_sanity"]["handoff_failures"] == 0.0
    assert line["budgets"]["compile_time_s"]["value"] == 6.0
    assert "error" not in line


def test_committed_disagg_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_disagg_cpu_
    sanity.json) satisfies the acceptance bar: headline 0 off-TPU, the
    split fleet's short-class decode p99 TPOT beats the unified fleet's
    (speedup > 1), both arms produced byte-identical tokens, every long
    request in the split arm actually took the handoff path with zero
    failures and the unified arm never handed off, budgets populated
    without violations."""
    from pathlib import Path

    path = (Path(__file__).parent.parent
            / "BENCH_decode_disagg_cpu_sanity.json")
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["disagg_ok"] is True
    assert sanity["identity_ok"] is True
    assert sanity["decode_tpot_p99_speedup"] > 1.0
    assert sanity["handoff_failures"] == 0
    wl = sanity["workload"]
    # every long request (n_long clients x long_reqs each) hopped, plus
    # the warm-up request; the unified arm's router counter stays 0 (the
    # bench gates on it before reporting, so handoffs here are split-arm)
    assert sanity["handoffs"] >= wl["n_long"] * wl["long_reqs"]
    by_key = {(r["arm"], r["class"]): r for r in sanity["rows"]}
    assert set(by_key) == {("unified+unified", "short"),
                           ("unified+unified", "long"),
                           ("prefill+decode", "short"),
                           ("prefill+decode", "long")}
    # the headline: pure decode ticks beat prefill-polluted ones on the
    # saturated short class
    uni = by_key[("unified+unified", "short")]
    split = by_key[("prefill+decode", "short")]
    assert split["tpot_p99_ms"] < uni["tpot_p99_ms"]
    assert uni["requests"] == split["requests"] == (
        wl["n_short"] * wl["short_reqs"])
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec


def test_pp_bench_cpu_contract():
    """bench_decode.py --mode pp (ISSUE 20) reuses the off-TPU contract:
    headline 0, the pp-vs-equal-chip-tp decode ratio, the stage-bytes
    check and the HLO mechanism verdict ride under cpu_sanity with
    budget fields populated, TPU evidence goes to its own tagged file."""
    line = bench.cpu_contract_line({
        "metric": "engine_pp_decode_tok_s_ratio_llama470m_c8_eqchip",
        "value": 0.96, "unit": "x", "backend": "cpu",
        "pp_ok": True, "identity_ok": True, "stage_bytes_ok": True,
        "mechanism_ok": True, "stage_bytes_ratio": 0.25,
        "ratios_vs_equal_chip_pp1": {"pp2": 0.96, "pp4": 0.94},
        "compile_time_s": 19.0, "step_time_s": 0.01,
        "rows": [{"pp": 1, "tp": 1, "chips": 1, "decode_tok_s": 2300.0},
                 {"pp": 2, "tp": 1, "chips": 2, "decode_tok_s": 1170.0}],
    }, tag="engine_decode_pp")
    assert line["value"] == 0.0 and line["unit"] == "x"
    assert line["cpu_sanity"]["pp_ok"] is True
    assert line["cpu_sanity"]["mechanism_ok"] is True
    assert line["budgets"]["compile_time_s"]["value"] == 19.0
    assert "error" not in line


def test_committed_pp_evidence_is_valid():
    """The committed CPU-sanity evidence (BENCH_decode_pp_cpu_sanity.
    json) satisfies the acceptance bar: headline 0 off-TPU, greedy
    tokens identical across every arm, per-stage KV bytes exactly
    kv_pool_bytes/pp (the servable-model-size multiplier), the
    stage-permute ppermute chain machine-asserted in the compiled tick
    HLO, and every pp arm's decode tok/s within 15% of the equal-chip
    pp=1 (tp-only) arm, budgets populated without violations."""
    from pathlib import Path

    path = (Path(__file__).parent.parent
            / "BENCH_decode_pp_cpu_sanity.json")
    rec = json.loads(path.read_text())
    assert rec["value"] == 0.0 and rec["backend"] == "cpu"
    sanity = rec["cpu_sanity"]
    assert sanity["pp_ok"] is True
    assert sanity["identity_ok"] is True
    assert sanity["stage_bytes_ok"] is True
    assert sanity["mechanism_ok"] is True
    # the acceptance bar: <= 15% decode tok/s cost at equal chips for
    # EVERY pipelined arm, with per-stage KV residency cut to 1/pp
    assert all(r >= 0.85
               for r in sanity["ratios_vs_equal_chip_pp1"].values())
    by_arm = {(r["pp"], r["tp"]): r for r in sanity["rows"]}
    wl = sanity["workload"]
    for pp in wl["pps"]:
        base, arm = by_arm[(1, pp)], by_arm[(pp, 1)]
        assert base["chips"] == arm["chips"] == pp  # equal-chip pairing
        assert arm["kv_stage_bytes"] == arm["kv_pool_bytes"] // pp
        assert base["kv_stage_bytes"] == base["kv_pool_bytes"]
        assert (arm["decode_tok_s"]
                >= 0.85 * base["decode_tok_s"])
    assert by_arm[(1, 1)]["chips"] == 1  # flat identity reference ran
    assert "compile_time_s" in rec["budgets"]
    assert "error" not in rec
