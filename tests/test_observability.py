"""Observability subsystem (ISSUE 4): span tracer ring/nesting + Chrome
trace validity, Prometheus registry (escaping, types, concurrency),
exporter endpoint + on-demand profiler trigger, flops accounting vs a
hand-counted config, the no-device-sync lint rule, watchdog trace dumps,
and the driver integration (trace phases present, /metrics fields on
pretrain and the generation server, bitwise loss parity on/off)."""

import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_llm_tpu.observability import flops as flops_mod
from megatron_llm_tpu.observability import registry as registry_mod
from megatron_llm_tpu.observability import trace as trace_mod
from megatron_llm_tpu.observability.exporter import MetricsExporter
from megatron_llm_tpu.observability.profiler import ProfileTrigger
from megatron_llm_tpu.observability.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# (a) span tracer: nesting, wraparound, Chrome-trace validity
# ---------------------------------------------------------------------------


def test_span_nesting_timestamps_contain():
    t = trace_mod.SpanTracer(capacity=64)
    with t.span("outer"):
        with t.span("inner"):
            pass
    events = t.snapshot()
    assert [name for _, name, *_ in events] == ["inner", "outer"]
    (_, _, in_ts, in_dur, _, _), (_, _, out_ts, out_dur, _, _) = events
    # the inner span's [ts, ts+dur] interval nests inside the outer's
    assert out_ts <= in_ts
    assert in_ts + in_dur <= out_ts + out_dur + 1e-9


def test_ring_buffer_wraparound():
    t = trace_mod.SpanTracer(capacity=16)
    for i in range(50):
        t.instant("e", i=i)
    assert len(t) == 16
    assert t.dropped == 34
    kept = [args["i"] for _, _, _, _, _, args in t.snapshot()]
    assert kept == list(range(34, 50))  # newest survive, oldest dropped


def test_snapshot_drain_starts_new_window():
    t = trace_mod.SpanTracer(capacity=16)
    t.instant("a")
    assert len(t.snapshot(drain=True)) == 1
    assert len(t) == 0
    t.instant("b")
    assert [n for _, n, *_ in t.snapshot()] == ["b"]


def test_chrome_trace_json_valid(tmp_path):
    t = trace_mod.SpanTracer(capacity=64)
    with t.span("phase", iteration=3):
        t.instant("mark")
    path = t.dump(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    by_ph = {}
    for e in doc["traceEvents"]:
        # every event carries the Chrome-trace required fields
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e) or e["ph"] == "M"
        by_ph.setdefault(e["ph"], []).append(e)
    (x,) = by_ph["X"]
    assert x["name"] == "phase" and x["dur"] >= 0
    assert x["args"] == {"iteration": 3}
    (i,) = by_ph["i"]
    assert i["name"] == "mark"
    # thread metadata row labels the recording thread
    (m,) = by_ph["M"]
    assert m["name"] == "thread_name"
    assert m["args"]["name"] == threading.current_thread().name
    assert doc["otherData"]["dropped_events"] == 0


def test_module_level_span_noop_when_unconfigured():
    trace_mod.disable()
    with trace_mod.span("x") as s:
        assert s is None  # shared null context
    trace_mod.instant("y")  # must not raise
    t = trace_mod.configure(capacity=32)
    try:
        with trace_mod.span("x"):
            pass
        assert len(t) == 1
    finally:
        trace_mod.disable()


def test_tracer_threads_labelled(tmp_path):
    t = trace_mod.SpanTracer(capacity=64)

    def work():
        with t.span("bg"):
            pass

    th = threading.Thread(target=work, name="my-worker")
    th.start()
    th.join()
    doc = t.to_chrome_trace()
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    # the worker thread has exited: its ident renders as thread-<id>
    assert any(e["args"]["name"].startswith(("my-worker", "thread-"))
               for e in metas)


# ---------------------------------------------------------------------------
# (b) registry: text format, escaping, types, concurrency
# ---------------------------------------------------------------------------


def test_prometheus_text_escaping():
    r = MetricsRegistry()
    r.gauge("odd-name", help="line one\nline \\two",
            labels={"path": 'a"b\\c\nd'}).set(1.5)
    text = r.render()
    # metric name sanitized into the Prometheus grammar
    assert "odd_name{" in text and "odd-name" not in text
    assert "# HELP odd_name line one\\nline \\\\two" in text
    assert 'path="a\\"b\\\\c\\nd"' in text
    assert text.endswith("\n")


def test_registry_types_and_conflicts():
    r = MetricsRegistry()
    c = r.counter("n_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        r.gauge("n_total")  # one name, one type
    assert r.counter("n_total") is c  # get-or-create


def test_histogram_cumulative_buckets():
    r = MetricsRegistry()
    h = r.histogram("lat", buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 0.7, 5.0):
        h.observe(v)
    text = r.render()
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1"} 3' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text
    assert "lat_sum 6.25" in text


def test_registry_concurrent_updates_exact():
    """The prefetch/writer/scheduler threads all publish concurrently;
    totals must be exact, not approximately right."""
    r = MetricsRegistry()
    c = r.counter("hits_total")
    g = r.gauge("depth")
    n_threads, per_thread = 8, 5000

    def work(k):
        for i in range(per_thread):
            c.inc()
            g.set(i)
            r.counter("labelled_total", labels={"t": str(k)}).inc()

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    for k in range(n_threads):
        assert r.counter("labelled_total",
                         labels={"t": str(k)}).value == per_thread


def test_publishing_switch_gates_timer_mirror():
    from megatron_llm_tpu.utils.timers import Timers

    reg = registry_mod.get_registry()
    reg.clear()
    registry_mod.set_publishing(False)
    try:
        t = Timers(1)
        t("quiet", 0).start()
        t("quiet").stop()
        t.gauge("quiet-gauge", 1.0)
        assert reg.names() == []
    finally:
        registry_mod.set_publishing(True)
    t = Timers(1)
    t("loud", 0).start()
    t("loud").stop()
    t.gauge("loud-gauge", 2.0)
    text = reg.render()
    assert 'mlt_timer_seconds_total{name="loud"}' in text
    assert 'mlt_driver_gauge{name="loud-gauge"} 2' in text


# ---------------------------------------------------------------------------
# (c) exporter endpoint + profile trigger
# ---------------------------------------------------------------------------


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def test_exporter_endpoint_smoke(tmp_path):
    r = MetricsRegistry()
    r.counter("smoke_total", help="smoke").inc(7)
    starts, stops = [], []
    trig = ProfileTrigger(str(tmp_path), default_steps=2, max_captures=2,
                          start_fn=starts.append, stop_fn=lambda: stops.append(1))
    ex = MetricsExporter(r, trig, host="127.0.0.1", port=0)
    port = ex.start()
    try:
        code, body, headers = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        assert "# TYPE smoke_total counter" in body
        assert "smoke_total 7" in body

        code, body, _ = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        code, body, _ = _get(f"http://127.0.0.1:{port}/profile?steps=3")
        assert code == 200 and json.loads(body)["accepted"]
        # second request while the first is pending -> 409
        code, body, _ = _get(f"http://127.0.0.1:{port}/profile")
        assert code == 409 and not json.loads(body)["accepted"]

        code, body, _ = _get(f"http://127.0.0.1:{port}/nope")
        assert code == 404
    finally:
        ex.stop()
    # driver side runs the armed window: start at a boundary, stop after N
    assert trig.maybe_start(iteration=5) is not None
    assert starts and "iter00000005" in starts[0]
    assert not trig.step_done() and not trig.step_done()
    assert trig.step_done() and stops == [1]


def test_profile_trigger_budget_and_close(tmp_path):
    starts, stops = [], []
    trig = ProfileTrigger(str(tmp_path), max_captures=1,
                          start_fn=starts.append, stop_fn=lambda: stops.append(1))
    assert trig.request(1)["accepted"]
    trig.maybe_start(0)
    trig.close()  # open window closed exactly once
    assert stops == [1]
    res = trig.request(1)
    assert not res["accepted"] and "budget" in res["error"]
    assert not trig.request(0)["accepted"]  # steps must be >= 1


def test_exporter_without_trigger_503():
    ex = MetricsExporter(MetricsRegistry(), None, host="127.0.0.1", port=0)
    port = ex.start()
    try:
        code, body, _ = _get(f"http://127.0.0.1:{port}/profile?steps=1")
        assert code == 503
    finally:
        ex.stop()


# ---------------------------------------------------------------------------
# (d) flops vs a hand-counted tiny config
# ---------------------------------------------------------------------------


def test_flops_formula_hand_counted():
    from megatron_llm_tpu.models import make_config

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=8, num_attention_heads=2,
        num_attention_heads_kv=1, ffn_hidden_size=16, vocab_size=32,
        seq_length=4, max_position_embeddings=8, tokenizer_type=None,
        micro_batch_size=2, global_batch_size=2,
    )
    # hand count: h=8, L=2, heads=2, kv=1, d=4, ffn=16, glu (swiglu) => 2
    # per layer: qkv 8*(2+2*1)*4=128; proj 2*4*8=64; mlp up 8*16*2=256;
    # mlp down 16*8=128  => 576;  embeddings (untied) 32*8*2=512
    assert flops_mod.param_count(cfg) == 576 * 2 + 512
    # 6*N + 6*L*h*s = 6*1664 + 6*2*8*4
    assert flops_mod.flops_per_token(cfg) == 6 * 1664 + 384
    assert flops_mod.flops_per_step(cfg) == (6 * 1664 + 384) * 2 * 4
    # MFU: known kind divides by its peak; unknown kind -> None
    tps = 1000.0
    mfu = flops_mod.mfu(cfg, tps, device_kind="TPU v5 lite")
    assert mfu == pytest.approx((6 * 1664 + 384) * tps / 197e12)
    assert flops_mod.mfu(cfg, tps, device_kind="cpu") is None
    assert flops_mod.mfu(cfg, 0.0, peak=1e12) is None
    # the driver's wrapper delegates here
    from megatron_llm_tpu.training import model_flops_per_token

    assert model_flops_per_token(cfg) == flops_mod.flops_per_token(cfg)


def test_peak_tables_single_source():
    """bench.py re-exports the flops.py peak tables — the measured MFU
    and the registry gauge must divide by the same numbers."""
    import bench

    assert bench.PEAK_BF16_FLOPS_BY_KIND is flops_mod.PEAK_BF16_FLOPS_BY_KIND
    assert bench.peak_flops() is None  # this process is pinned to the CPU
    assert flops_mod.device_peak_flops("TPU v5") == 459e12
    assert flops_mod.device_peak_flops("cpu") is None
    # exact device_kind only: a measuring path does not divide by a guess
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        flops_mod.device_peak_flops("TPU v5e somethingnew")
    # every device the rate was produced on shares the bill
    from megatron_llm_tpu.models import make_config

    cfg = make_config("llama2", num_layers=2, hidden_size=64,
                      num_attention_heads=4, vocab_size=128, seq_length=32)
    one = flops_mod.mfu(cfg, 1e4, device_kind="TPU v5 lite")
    assert flops_mod.mfu(cfg, 1e4, device_kind="TPU v5 lite",
                         n_devices=4) == pytest.approx(one / 4)


# ---------------------------------------------------------------------------
# (e) linter: no device syncs inside observability/
# ---------------------------------------------------------------------------


def test_linter_forbids_device_sync_in_observability(tmp_path, capsys):
    from tools.linter import lint_file

    bad = tmp_path / "observability" / "thing.py"
    bad.parent.mkdir()
    bad.write_text("import jax\nx = jax.device_" + "get(y)\n")
    assert lint_file(str(bad)) == 1
    assert "device sync in observability/" in capsys.readouterr().out

    # the same line OUTSIDE an observability dir is fine
    ok = tmp_path / "elsewhere.py"
    ok.write_text("x = jax.device_" + "get(y)\n")
    assert lint_file(str(ok)) == 0

    blocked = tmp_path / "observability" / "wait.py"
    blocked.write_text("arr.block_until_" + "ready()\n")
    assert lint_file(str(blocked)) == 1


def test_observability_package_passes_linter():
    from tools.linter import lint_file

    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "megatron_llm_tpu", "observability")
    issues = 0
    for name in os.listdir(pkg):
        if name.endswith(".py"):
            issues += lint_file(os.path.join(pkg, name))
    assert issues == 0


# ---------------------------------------------------------------------------
# (f) watchdog dumps the trace ring buffer on expiry
# ---------------------------------------------------------------------------


def test_watchdog_dumps_trace_on_expiry(tmp_path):
    import io

    from megatron_llm_tpu.resilience.watchdog import StepWatchdog

    tracer = trace_mod.SpanTracer(capacity=32)
    with tracer.span("data-wait"):
        pass
    trace_path = str(tmp_path / "trace_watchdog.json")
    stream = io.StringIO()
    exits = []
    dog = StepWatchdog(
        min_deadline=0.05, first_deadline=0.05, multiplier=1.0,
        trace_dump_fn=lambda: tracer.dump(trace_path, drain=False),
        exit_fn=exits.append, stream=stream,
    ).start()
    dog.arm(first=True)
    for _ in range(100):
        if exits:
            break
        import time

        time.sleep(0.05)
    assert exits == [43]
    out = stream.getvalue()
    assert "dumping" in out  # stack dump ran
    assert f"span trace dumped to {trace_path}" in out
    doc = json.load(open(trace_path))
    assert any(e["name"] == "data-wait" for e in doc["traceEvents"])
    # drain=False: the ring still holds the evidence
    assert len(tracer) == 1


def test_watchdog_trace_fallback_text(tmp_path):
    """Without --trace_dir the watchdog still prints a text timeline
    when a process-wide tracer exists."""
    import io
    import time

    from megatron_llm_tpu.resilience.watchdog import StepWatchdog

    tracer = trace_mod.configure(capacity=32)
    try:
        with trace_mod.span("dispatch", iteration=9):
            pass
        stream = io.StringIO()
        exits = []
        dog = StepWatchdog(
            min_deadline=0.05, first_deadline=0.05, multiplier=1.0,
            exit_fn=exits.append, stream=stream,
        ).start()
        dog.arm(first=True)
        for _ in range(100):
            if exits:
                break
            time.sleep(0.05)
        assert exits == [43]
        out = stream.getvalue()
        assert "TRACE: last" in out and "dispatch" in out
    finally:
        trace_mod.disable()


# ---------------------------------------------------------------------------
# (g) driver integration: trace phases, /metrics fields, bitwise parity
# ---------------------------------------------------------------------------


def _provider(scrape_at=None, scraped=None):
    """Synthetic deterministic data provider; optionally scrapes the live
    /metrics endpoint from inside the run (the prefetch worker thread)."""

    def provider(cfg, tokenizer, consumed):
        gbs, seq = cfg.training.global_batch_size, cfg.data.seq_length
        rng = np.random.default_rng(0)
        pool = [{
            "tokens": rng.integers(1, 512, (gbs, seq)).astype(np.int32),
            "labels": rng.integers(1, 512, (gbs, seq)).astype(np.int32),
            "loss_mask": np.ones((gbs, seq), np.float32),
        } for _ in range(2)]

        def gen():
            i = 0
            while True:
                if scrape_at is not None and i == scrape_at and not scraped:
                    from megatron_llm_tpu.observability import exporter

                    ex = exporter.active_exporter()
                    if ex is not None:
                        _, body, _ = _get(
                            f"http://127.0.0.1:{ex.port}/metrics")
                        scraped["text"] = body
                yield pool[i % 2]
                i += 1

        return gen(), None

    return provider


def _tiny_cfg(train_iters=10, **logging):
    from megatron_llm_tpu.models import make_config

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, vocab_size=512,
        seq_length=32, max_position_embeddings=64, params_dtype="float32",
        use_flash_attn=False, micro_batch_size=2, global_batch_size=2,
        train_iters=train_iters, log_interval=2, eval_interval=0,
        tokenizer_type=None,
    )
    # the test harness exposes 8 virtual CPU devices; this loop is a
    # single-device run (gbs 2 does not divide dp 8)
    cfg.parallel.data_parallel_size = 1
    for k, v in logging.items():
        setattr(cfg.logging, k, v)
    return cfg


def test_pretrain_trace_and_metrics_end_to_end(tmp_path):
    """ISSUE 4 acceptance: a 10-step run with --trace_dir emits Chrome
    trace JSON whose spans include the async loop's phases, and a live
    /metrics scrape serves steady_mfu / tokens_per_sec / goodput."""
    from megatron_llm_tpu.training import pretrain

    trace_dir = str(tmp_path / "trace")
    scraped = {}
    cfg = _tiny_cfg(trace_dir=trace_dir, trace_steps=4, metrics_port=0)
    cfg.checkpoint.save = str(tmp_path / "ckpt")
    cfg.checkpoint.save_interval = 5
    cfg.checkpoint.async_save = True
    result = pretrain(cfg, data_iterators_provider=_provider(
        scrape_at=6, scraped=scraped))

    assert result["iteration"] == 10
    assert result["metrics_port"] and result["tokens_per_sec"] > 0
    assert result["steady_mfu"] is None  # CPU: no made-up MFU

    names = set()
    files = sorted(os.listdir(trace_dir))
    assert any(f.startswith("trace_final") for f in files)
    for f in files:
        if not f.endswith(".json"):
            continue
        doc = json.load(open(os.path.join(trace_dir, f)))
        assert isinstance(doc["traceEvents"], list)  # loads in Perfetto
        for e in doc["traceEvents"]:
            assert "ph" in e and "name" in e
        names |= {e["name"] for e in doc["traceEvents"]}
    for phase in ("data-wait", "dispatch", "metric-drain", "ckpt-flush",
                  "ckpt-write", "place-batch", "step-begin"):
        assert phase in names, f"missing span {phase} in {sorted(names)}"

    assert "text" in scraped, "mid-run /metrics scrape did not happen"
    for field in ("mlt_tokens_per_sec", "mlt_steady_mfu",
                  "mlt_goodput_fraction", "mlt_lm_loss", "mlt_iteration",
                  "mlt_batches_placed_total", "mlt_timer_seconds_total"):
        assert field in scraped["text"], f"missing {field} in /metrics"
    # exporter shut down with the run
    from megatron_llm_tpu.observability import exporter

    assert exporter.active_exporter() is None


def test_loss_bitwise_identical_with_observability(tmp_path):
    """ISSUE 4 acceptance: the loss trajectory with full observability on
    is bitwise-identical to all-off — instruments observe the loop, they
    never sit in its numerics."""
    from megatron_llm_tpu.training import pretrain

    off = pretrain(_tiny_cfg(), data_iterators_provider=_provider())
    on = pretrain(
        _tiny_cfg(trace_dir=str(tmp_path / "t"), trace_steps=3,
                  metrics_port=0),
        data_iterators_provider=_provider())
    assert off["loss_series"] == on["loss_series"]  # exact float equality
    assert float(off["last_metrics"]["lm loss"]) == float(
        on["last_metrics"]["lm loss"])


def test_generation_server_metrics_endpoint():
    """ISSUE 4 acceptance: /metrics on the generation server serves
    Prometheus text including engine slot occupancy."""
    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.models import init_model_params, make_config
    from tests.test_generation import VOCAB, ToyTokenizer

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        params_dtype="float32", use_flash_attn=False,
    )
    cfg.inference.max_batch_slots = 4
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer())
    srv = MegatronServer(engine)
    port = srv.start_background(port=0)
    try:
        code, body, headers = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        for field in ("mlt_engine_active_slots", "mlt_engine_max_slots",
                      "mlt_engine_queued_requests", "mlt_engine_free_pages",
                      "mlt_engine_pool_pages",
                      # ISSUE 5: prefix-cache telemetry
                      "mlt_engine_prefix_hit_tokens_total",
                      "mlt_engine_prefix_miss_tokens_total",
                      "mlt_engine_pages_cached",
                      "mlt_engine_pages_cow_copies_total",
                      # ISSUE 11: ragged-tick launch telemetry
                      "mlt_engine_tick_launches_total",
                      "mlt_engine_prefill_tokens_per_tick",
                      # ISSUE 12: honest TTFT decomposition histograms
                      "mlt_engine_queue_wait_seconds",
                      "mlt_engine_prefill_compute_seconds",
                      "mlt_engine_preempted_seconds",
                      # ISSUE 13: quantized-KV capacity telemetry
                      "mlt_engine_kv_pool_bytes",
                      "mlt_engine_kv_scale_bytes",
                      "mlt_engine_kv_dtype_info",
                      # ISSUE 15: compute/collective overlap mode
                      "mlt_tp_overlap_info",
                      # ISSUE 17: pipelined-dispatch telemetry
                      "mlt_engine_host_gap_seconds",
                      "mlt_engine_inflight_ticks",
                      "mlt_engine_tick_pipeline_depth",
                      # ISSUE 20: pipeline-parallel serving geometry
                      "mlt_engine_pp_stages",
                      "mlt_engine_kv_stage_bytes"):
            assert field in body, f"missing {field}"
        # an unpipelined engine reports one stage and a full-pool stage
        assert "mlt_engine_pp_stages 1" in body
        assert "mlt_engine_max_slots 4" in body
        assert 'mlt_engine_kv_dtype_info{kv_dtype="bf16"} 1' in body
        # a no-mesh engine reports the off mode at tp=1
        assert 'mlt_tp_overlap_info{mode="off",tp="1"} 1' in body
        # /health still answers alongside
        code, body, _ = _get(f"http://127.0.0.1:{port}/health")
        health = json.loads(body)
        assert code == 200 and health["status"] == "ok"
        # ISSUE 13: /health names the KV storage mode + byte budget
        assert health["kv_dtype"] == "bf16"
        assert health["kv_pool_bytes"] > 0
        assert health["kv_scale_bytes"] == 0
        assert health["peak_active_slots"] == 0
        # ISSUE 17: /health names the configured pipeline depth
        assert health["tick_pipeline_depth"] == 0
        # ISSUE 20: /health names the serving pipeline geometry — an
        # unpipelined engine reports one stage owning the whole pool
        assert health["pp"] == 1 and health["stages"] == 1
        assert health["kv_stage_bytes"] == health["kv_pool_bytes"]
    finally:
        srv.stop()


def test_engine_tick_metrics_count():
    """The engine's registry counters advance with real generations."""
    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.models import init_model_params, make_config
    from tests.test_generation import VOCAB, ToyTokenizer

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer())
    reg = registry_mod.get_registry()
    ticks0 = reg.counter("mlt_engine_ticks_total").value
    req0 = reg.counter("mlt_engine_requests_total").value
    engine.submit([5, 6, 7], 4, use_eod_for_termination=False)
    engine.run_until_idle()
    assert reg.counter("mlt_engine_requests_total").value == req0 + 1
    assert reg.counter("mlt_engine_ticks_total").value >= ticks0 + 4
    assert reg.gauge("mlt_engine_active_slots").value == 0  # drained


def test_on_demand_profile_trigger_in_pretrain(tmp_path, monkeypatch):
    """A /profile-style request armed before the run captures a bounded
    window at a step boundary inside the real loop."""
    from megatron_llm_tpu.observability import profiler as prof_mod
    from megatron_llm_tpu.training import pretrain

    calls = {"start": [], "stop": 0}

    def fake_start(logdir):
        calls["start"].append(logdir)

    def fake_stop():
        calls["stop"] += 1

    monkeypatch.setattr(prof_mod, "_jax_start", fake_start)
    monkeypatch.setattr(prof_mod, "_jax_stop", fake_stop)

    real_init = prof_mod.ProfileTrigger.__init__

    def patched_init(self, out_dir, **kw):
        kw.setdefault("start_fn", fake_start)
        kw.setdefault("stop_fn", fake_stop)
        real_init(self, out_dir, **kw)
        self.request(2)  # as if /profile?steps=2 landed before step 0

    monkeypatch.setattr(prof_mod.ProfileTrigger, "__init__", patched_init)
    pretrain(_tiny_cfg(train_iters=6), data_iterators_provider=_provider())
    assert len(calls["start"]) == 1
    assert "ondemand_000" in calls["start"][0]
    assert calls["stop"] == 1  # stopped after its window, not leaked


# ---------------------------------------------------------------------------
# (h) bench contract (tier-1 entries; the <3% gate runs in the slow lane)
# ---------------------------------------------------------------------------


def test_instrument_cost_microbench():
    """The per-step instrument bill, measured deterministically: replay
    one driver iteration's full instrumentation (spans, timer mirrors,
    gauges, trigger checks, amortized window dump) and time it alone.
    Tens of µs — far inside 3% of any real step."""
    import bench_observability as bo

    cost = bo.measure_instrument_cost(steps=500)
    # generous cap: even a 10ms CPU micro-step keeps 300µs/step inside 3%
    assert cost["instrument_cost_us_per_step"] < 300.0, cost


@pytest.mark.slow
def test_observability_overhead_gate(tmp_path):
    """ISSUE 4 acceptance gate: < 3% steps/sec overhead with full
    instrumentation on, at the bench's own CPU sanity shape.

    A wall-clock off/on A/B on this shared single-core host has a noise
    floor well above 3% (the bench's alternating-pair median tames it
    for evidence runs, but not enough for a hard CI gate), so the gate
    is asserted deterministically: the measured per-step instrument cost
    must be < 3% of the measured real step time — the same two numbers
    the wall-clock ratio divides, without the host drift between runs.
    The bitwise-parity half of the acceptance runs in the tier-1 lane
    (test_loss_bitwise_identical_with_observability)."""
    import bench_observability as bo
    from megatron_llm_tpu.models import make_config

    def make_cfg(iters):
        cfg = make_config(
            "llama2", num_layers=2, hidden_size=256,
            num_attention_heads=4, num_attention_heads_kv=4,
            ffn_hidden_size=512, vocab_size=1024, seq_length=128,
            max_position_embeddings=128, params_dtype="float32",
            use_flash_attn=False, micro_batch_size=4, global_batch_size=4,
            train_iters=iters, log_interval=10, eval_interval=0,
            tokenizer_type=None,
        )
        cfg.parallel.data_parallel_size = 1
        return cfg

    base = bo.run_mode(make_cfg, 1024, 128, 20, instrumented=False)
    step_us = 1e6 / max(base["steps_per_sec"] or 1e-9, 1e-9)
    cost = bo.measure_instrument_cost(steps=2000,
                                      trace_dir=str(tmp_path / "t"))
    overhead_pct = cost["instrument_cost_us_per_step"] / step_us * 100.0
    assert overhead_pct < bo.GATE_OVERHEAD_PCT, (cost, step_us)
