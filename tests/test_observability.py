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
    """One span(), two sinks: with no ring configured a span is the bare
    profiler annotation (visible to any jax.profiler capture, nothing
    recorded in-process); with a ring it is recorded there as well."""
    from jax.profiler import TraceAnnotation

    trace_mod.disable()
    assert trace_mod.get_tracer() is None
    with trace_mod.span("x", tick=3) as s:
        assert isinstance(trace_mod.span("x"), TraceAnnotation)
        assert s is not trace_mod.span("x")  # no shared state
    trace_mod.instant("y")  # ring-only: a no-op without a ring
    t = trace_mod.configure(capacity=32)
    try:
        with trace_mod.span("x", tick=4):
            pass
        assert [(e[1], e[5]) for e in t.snapshot()] == [("x", {"tick": 4})]
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


def _toy_serving_model():
    import jax

    from megatron_llm_tpu.models import init_model_params, make_config
    from tests.test_generation import VOCAB

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        params_dtype="float32", use_flash_attn=False,
    )
    return cfg, init_model_params(cfg, jax.random.PRNGKey(0))


def test_multi_chunk_tick_counter_counts_only_packed_ticks():
    """ISSUE 28: mlt_engine_prefill_multi_chunk_ticks_total counts the
    ticks that prefilled more than one chunk, no others, and is served."""
    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.observability import registry as obs_registry
    from tests.test_generation import ToyTokenizer

    cfg, params = _toy_serving_model()
    # two chunks of slots: the default capacity is two chunks a tick
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                      max_slots=32, prefill_chunk=16)
    reg = obs_registry.get_registry()

    def read():
        return (reg.counter(
            "mlt_engine_prefill_multi_chunk_ticks_total").value,
            reg.counter("mlt_engine_tick_kind_total",
                        labels={"kind": "prefill"}).value)

    def run(n_prompt):
        req = engine.submit([2 + j % 60 for j in range(n_prompt)], 3,
                            top_k=1, termination_id=10 ** 9)
        engine.run_until_idle()
        req.result(timeout=120)

    multi0, pre0 = read()
    run(10)                   # one short chunk: a prefill tick, not packed
    assert read() == (multi0, pre0 + 1)
    run(40)                   # 48 rows: a tick of two chunks, then one
    multi1, pre1 = read()
    assert (multi1, pre1) == (multi0 + 1, pre0 + 3)
    srv = MegatronServer(engine)
    port = srv.start_background(port=0)
    try:
        _, body, _ = _get(f"http://127.0.0.1:{port}/metrics")
    finally:
        srv.stop()
    assert f"mlt_engine_prefill_multi_chunk_ticks_total {multi1:g}" in body


def test_generation_server_metrics_endpoint():
    """ISSUE 4 acceptance: /metrics on the generation server serves
    Prometheus text including engine slot occupancy."""
    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from tests.test_generation import ToyTokenizer

    cfg, params = _toy_serving_model()
    cfg.inference.max_batch_slots = 4
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
                      # the one-tick lag's telemetry
                      "mlt_engine_host_gap_seconds",
                      "mlt_engine_inflight_ticks",
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


def test_instrument_cost_microbench(monkeypatch, tmp_path):
    """The per-step instrument bill, COUNTED: replay one driver
    iteration's full instrumentation (spans, timer mirrors, gauges,
    trigger checks, the amortized window dump, one flight record's life)
    and count what it does, which is the same on a loaded host as on a
    quiet one (microseconds are not: the suite runs six workers on shared
    cores, and the least of five timings still crossed its bound).  A
    step is 18 clock reads, 6 registry look-ups with an update each, 4
    tracer events and a tenth of a window dump; at the ~0.1-2 us each of
    ``-k span_cost`` that is tens of microseconds, far inside 3% of any
    real step (the slow lane's gate times it against a measured step)."""
    import collections
    import time
    import types

    import bench_observability as bo
    from megatron_llm_tpu.observability import flight as flight_mod
    from megatron_llm_tpu.utils import timers as timers_mod

    work = collections.Counter()

    def counted(what, real):
        def call(*a, **kw):
            work[what] += 1
            return real(*a, **kw)
        return call

    clock = types.SimpleNamespace(**{
        k: getattr(time, k) for k in dir(time) if not k.startswith("_")})
    for name in ("perf_counter", "perf_counter_ns", "monotonic",
                 "monotonic_ns", "time", "time_ns", "thread_time"):
        setattr(clock, name, counted("clock reads", getattr(time, name)))
    for mod in (trace_mod, flight_mod, timers_mod):
        monkeypatch.setattr(mod, "time", clock)
    monkeypatch.setattr(registry_mod.MetricsRegistry, "_get", counted(
        "registry look-ups", registry_mod.MetricsRegistry._get))
    for cls, update in ((registry_mod.Counter, "inc"),
                        (registry_mod.GaugeMetric, "set"),
                        (registry_mod.Histogram, "observe")):
        monkeypatch.setattr(cls, update, counted(
            "registry updates", getattr(cls, update)))

    tracer = trace_mod.configure(capacity=4096)
    monkeypatch.setattr(tracer, "dump", counted("window dumps", tracer.dump))
    was_publishing = registry_mod.publishing()
    registry_mod.set_publishing(True)
    try:
        timers = timers_mod.Timers(1)
        flight = flight_mod.FlightRecorder(capacity=256,
                                           events_per_request=64)
        trigger = ProfileTrigger(str(tmp_path), start_fn=lambda d: None,
                                 stop_fn=lambda: None)
        steps = 30
        work.clear()        # making the instruments is not a step's cost
        for i in range(steps):
            bo.instrument_step(i, tracer, timers, flight, trigger,
                               str(tmp_path))
        work["tracer events"] = tracer._total
    finally:
        trace_mod.disable()
        registry_mod.set_publishing(was_publishing)
    assert {k: v / steps for k, v in work.items()} == {
        "clock reads": 18, "registry look-ups": 6, "registry updates": 6,
        "tracer events": 4, "window dumps": 0.1}, work


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


# ---------------------------------------------------------------------------
# (i) program spans on the profiler's clock (ISSUE 24): the engine step as
# a tree of spans in a jax.profiler capture and in the ring, the counters
# at the same boundaries, the bucket ladder, the compile counter, /profile
# ---------------------------------------------------------------------------

PHASES = ("admit", "plan", "launch", "fetch", "apply")
PLAN_PARTS = ("prefill", "pages", "upload")
CPU_SIDES = ("dispatch", "apply")


def _capture_spans(path, names):
    """[(name, line index, start_ns, end_ns, stats)] of the events called
    one of ``names`` on plane /host:CPU of an xplane file."""
    import warnings

    from jax.profiler import ProfileData

    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name in names:
                        out.append((e.name, i, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _counts(reg):
    out = {ph: reg.histogram("mlt_engine_tick_phase_seconds",
                             labels={"phase": ph}).snapshot()[2]
           for ph in PHASES}
    out["ticks"] = reg.counter("mlt_engine_ticks_total").value
    for k in ("decode", "prefill"):
        out[k] = reg.counter("mlt_engine_tick_kind_total",
                             labels={"kind": k}).value
    for side in CPU_SIDES:
        out["cpu:" + side] = reg.histogram(
            "mlt_engine_tick_host_cpu_seconds",
            labels={"side": side}).snapshot()[2]
    for part in PLAN_PARTS:
        _, out["sum:" + part], out["part:" + part] = reg.histogram(
            "mlt_engine_plan_part_seconds",
            labels={"part": part}).snapshot()
    out["sum:plan"] = reg.histogram(
        "mlt_engine_tick_phase_seconds",
        labels={"phase": "plan"}).snapshot()[1]
    return out


@pytest.fixture(scope="module")
def traced_serving_run(tmp_path_factory):
    """Two streamed requests of three ticks each through the real server,
    under a jax.profiler capture, with a ring configured too."""
    import glob
    import http.client
    import time

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

    def ask(prompt="trace me"):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("PUT", "/api", body=json.dumps(
            {"prompts": [prompt], "tokens_to_generate": 3, "top_k": 1,
             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        assert resp.status == 200, body

    logdir = str(tmp_path_factory.mktemp("capture"))
    old = trace_mod.get_tracer()
    reg = registry_mod.get_registry()
    try:
        ask()  # compile outside the capture
        ring = trace_mod.configure(capacity=4096)
        # the client has its last event before the scheduler thread has
        # left that step (the phase histogram is fed at its very end);
        # the loop holds _drive_lock over a whole step, so under it the
        # counters are those of whole steps
        with engine._drive_lock:
            before = _counts(reg)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the program's spans, not every call
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            ask()
            ask("and me too")  # the loop's wait between the two is inside
            # the client has its last event before the scheduler thread
            # has closed that step's spans: let it reach its idle wait, or
            # the capture misses spans the ring still gets
            time.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        with engine._drive_lock:
            after = _counts(reg)
        ring_events = ring.snapshot()
    finally:
        trace_mod._TRACER = old
        srv.stop()
    (path,) = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    names = {"engine-step", "engine-admit", "engine-plan",
             "engine-ragged-tick", "engine-launch", "engine-fetch",
             "engine-apply", "engine-wait", "serve-write",
             "serve-api-stream"}
    return {"capture": _capture_spans(path, names), "ring": ring_events,
            "page_size": engine.page_size,
            "delta": {k: after[k] - before[k] for k in after}}


def _children(spans, parent):
    name, line, s, e, _ = parent
    return [c for c in spans
            if c[1] == line and c is not parent and s <= c[2] and c[3] <= e]


def test_capture_holds_the_engine_step_tree(traced_serving_run):
    spans = traced_serving_run["capture"]
    steps = [x for x in spans if x[0] == "engine-step"]
    ticked = [x for x in steps
              if any(c[0] == "engine-launch" for c in _children(spans, x))]
    assert len(ticked) >= 3, [x[0] for x in spans]
    assert len({x[1] for x in steps}) == 1, "one scheduler thread"
    # a step names the tick it lands (the ticks applied before it began)
    # and launches the one after: it runs one tick ahead of the host
    ticks = [x[4]["tick"] for x in ticked]
    assert ticks == sorted(ticks)
    lagged = 0
    for step in ticked:
        kids = sorted(_children(spans, step), key=lambda c: c[2])
        order = [c[0] for c in kids]
        assert order[:4] == ["engine-admit", "engine-plan",
                             "engine-ragged-tick", "engine-launch"]
        # then nothing (no tick was in flight) or the fetch and apply of
        # the tick before, beside the one just launched
        assert order[4:] in ([], ["engine-fetch", "engine-apply"]), order
        tick, launch = kids[2], kids[3]
        assert tick[2] <= launch[2] and launch[3] <= tick[3]
        assert launch[4]["tick"] == step[4]["tick"] + len(order[4:]) // 2
        for landed in kids[4:]:
            lagged += landed[0] == "engine-fetch"
            assert landed[2] >= tick[3]
            assert landed[4]["tick"] == launch[4]["tick"] - 1
        # siblings in order, none overlapping
        seq = kids[:2] + kids[3:]
        assert all(a[3] <= b[2] for a, b in zip(seq, seq[1:]))
    assert lagged >= 3, "no tick was launched before the last was fetched"
    launches = [c for x in ticked for c in _children(spans, x)
                if c[0] == "engine-launch"]
    numbers = [c[4]["tick"] for c in launches]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    # a step that launches nothing lands what is in flight, fetch then apply
    landing = [x for x in steps if x not in ticked and _children(spans, x)]
    assert landing and all(
        [c[0] for c in sorted(_children(spans, x), key=lambda c: c[2])][-2:]
        == ["engine-fetch", "engine-apply"] for x in landing)
    # each prompt rides one tick as a bucketed prefill chunk; the other
    # ticks are decode-only, one live row
    pre = [c[4] for c in launches if c[4]["prefill_rows"] > 0]
    page = traced_serving_run["page_size"]  # prompts fill whole pages
    assert [a["prefill_tokens"] for a in pre] == [page, page]
    assert all(a["prefill_rows"] >= a["prefill_tokens"] for a in pre)
    dec = [c[4] for c in launches if c[4]["prefill_rows"] == 0]
    assert len(dec) >= 4 and all(
        a["decode_rows"] == 1 and a["prefill_tokens"] == 0 for a in dec)


def test_capture_holds_handler_thread_writes(traced_serving_run):
    spans = traced_serving_run["capture"]
    sched = {x[1] for x in spans if x[0] == "engine-step"}
    writes = [x for x in spans if x[0] == "serve-write"]
    assert all(x[1] not in sched for x in writes)
    # a handler thread's writes (a stream's first frame, its terminal)
    # lie inside its request's span, same thread
    apis = [x for x in spans if x[0] == "serve-api-stream"]
    own = [w for w in writes if any(
        a[1] == w[1] and a[2] <= w[2] and w[3] <= a[3] for a in apis)]
    assert apis and len(own) >= 2 * len(apis)
    # every other write is the one stream writer's: a pass a tick, on a
    # thread that serves no request
    passes = [w for w in writes if w not in own]
    assert len(passes) >= 3 and len({w[1] for w in passes}) == 1
    assert not {w[1] for w in passes} & {a[1] for a in apis}
    # the loop's idle wait is a span too: no work is told from host slow
    assert any(x[0] == "engine-wait" and x[1] in sched for x in spans)


def test_ring_holds_the_same_spans(traced_serving_run):
    from collections import Counter

    ring = Counter(e[1] for e in traced_serving_run["ring"] if e[0] == "X")
    cap = Counter(x[0] for x in traced_serving_run["capture"])
    for name in ("engine-step", "engine-admit", "engine-plan",
                 "engine-ragged-tick", "engine-launch", "engine-fetch",
                 "engine-apply", "serve-write", "serve-api-stream"):
        assert ring[name] == cap[name] > 0, (name, ring[name], cap[name])
    launch = [e for e in traced_serving_run["ring"]
              if e[1] == "engine-launch"]
    assert set(launch[0][5]) == {"tick", "prefill_rows", "prefill_tokens",
                                 "decode_rows"}


def test_phase_and_kind_counters_add_up_to_ticks(traced_serving_run):
    d = traced_serving_run["delta"]
    assert d["ticks"] >= 3
    for ph in PHASES:
        assert d[ph] == d["ticks"], (ph, d)
    assert d["decode"] + d["prefill"] == d["ticks"]
    assert d["prefill"] == 2 and d["decode"] >= 4  # one prompt a request


def test_plan_parts_and_host_cpu_observe_once_a_tick(traced_serving_run):
    d = traced_serving_run["delta"]
    for part in PLAN_PARTS:
        assert d["part:" + part] == d["ticks"], (part, d)
    for side in CPU_SIDES:
        assert d["cpu:" + side] == d["ticks"], (side, d)
    # the parts lie inside the phase: what is left is its waits for the
    # engine's lock
    parts = sum(d["sum:" + part] for part in PLAN_PARTS)
    assert 0 < parts <= d["sum:plan"]


def test_ring_nests_plans_three_parts_in_engine_plan(traced_serving_run):
    """A ring dump holds ``plan-prefill``, ``plan-pages`` and (a step that
    launches) ``plan-upload`` inside their step's ``engine-plan``, in
    that order, none overlapping."""
    ring = [e for e in traced_serving_run["ring"] if e[0] == "X"]
    plans = [e for e in ring if e[1] == "engine-plan"]
    launches = [e for e in ring if e[1] == "engine-launch"]
    assert plans and len({e[4] for e in plans}) == 1
    launched = 0
    for plan in plans:
        t0, t1 = plan[2], plan[2] + plan[3]
        kids = sorted((e for e in ring if e[4] == plan[4]
                       and e[1].startswith("plan-")
                       and t0 <= e[2] and e[2] + e[3] <= t1),
                      key=lambda e: e[2])
        names = [e[1] for e in kids]
        assert names in (["plan-prefill", "plan-pages", "plan-upload"],
                         ["plan-prefill", "plan-pages"]), names
        assert all(a[2] + a[3] <= b[2] for a, b in zip(kids, kids[1:]))
        launched += len(names) == 3
    assert launched == len(launches) >= 3
    # every plan-* span of the run has an engine-plan around it
    assert sum(e[1].startswith("plan-") for e in ring) == \
        2 * len(plans) + launched


def test_latency_ladder_quantiles_within_15_percent():
    import random

    ladder = registry_mod.LATENCY_BUCKETS
    assert len(ladder) == 49
    assert ladder[0] == pytest.approx(1e-4) and ladder[-1] == pytest.approx(1e2)
    assert all(b / a == pytest.approx(10 ** 0.125)
               for a, b in zip(ladder, ladder[1:]))
    rng = random.Random(24)
    # a latency-shaped sample: lognormal around 30 ms with a long tail
    xs = sorted(rng.lognormvariate(-3.5, 1.2) for _ in range(1000))
    h = registry_mod.Histogram(ladder)
    for x in xs:
        h.observe(x)
    cum, total, count = h.snapshot()
    assert count == 1000 and total == pytest.approx(sum(xs))
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = xs[int(q * 1000) - 1]
        assert h.quantile(q) == pytest.approx(exact, rel=0.15), q
    assert registry_mod.Histogram(ladder).quantile(0.5) is None


def test_engine_seconds_histograms_share_the_ladder(traced_serving_run):
    """Once an engine has been built (the fixture's), every
    mlt_engine_*_seconds histogram on /metrics has the ladder's 49 bounds
    and +Inf."""
    import re

    text = registry_mod.get_registry().render()
    seen = {}
    for m in re.finditer(
            r'^(mlt_engine_\w+_seconds)_bucket\{(.*?)le="([^"]+)"\} ',
            text, re.M):
        seen.setdefault((m.group(1), m.group(2)), []).append(m.group(3))
    names = {k[0] for k in seen}
    assert {"mlt_engine_ttft_seconds", "mlt_engine_queue_wait_seconds",
            "mlt_engine_prefill_compute_seconds",
            "mlt_engine_preempted_seconds", "mlt_engine_host_gap_seconds",
            "mlt_engine_tick_phase_seconds",
            "mlt_engine_tick_host_cpu_seconds",
            "mlt_engine_plan_part_seconds"} <= names
    for key, les in seen.items():
        assert len(les) == 50 and les[-1] == "+Inf", key
    assert "INCLUDES the fetch" in text  # host_gap's help says what it is


def test_compile_counter_counts_new_shapes_only():
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.observability.compiles import (
        install_compile_counter,
    )

    install_compile_counter()
    install_compile_counter()  # a second call must not count double
    reg = registry_mod.get_registry()
    n = reg.counter("mlt_jit_compiles_total")
    sec = reg.counter("mlt_jit_compile_seconds_total")
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    a, b = np.ones((7, 13), np.float32), np.ones((11, 5), np.float32)
    n0, s0 = n.value, sec.value
    f(a).block_until_ready()
    assert n.value == n0 + 1 and sec.value > s0
    f(a).block_until_ready()                 # a repeat: no compile
    assert n.value == n0 + 1
    f(b).block_until_ready()                 # a new shape: one more
    assert n.value == n0 + 2
    text = reg.render()
    assert "mlt_jit_compiles_total" in text
    assert "mlt_jit_compile_seconds_total" in text


@pytest.mark.parametrize("ring,capture,cap_us", [
    (False, False, 5.0),    # the bare annotation's atomic read
    (True, False, 25.0),    # + a ring record
    (False, True, 25.0),    # + an event in a live jax.profiler capture
    (True, True, 25.0),
], ids=["off", "ring", "capture", "ring+capture"])
def test_span_cost(ring, capture, cap_us, tmp_path):
    """One span in each of its four states; best of five rounds, generous
    caps for a shared CPU.  `-s` prints the figure (PERF.md section 6 has
    the chip machine's)."""
    import time

    import jax

    trace_mod.disable()
    if capture:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the span's bill, not the tracer's
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    if ring:
        trace_mod.configure(capacity=65536)
    try:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(2000):
                with trace_mod.span("engine-launch", prefill_rows=0,
                                    decode_rows=i):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000)
    finally:
        trace_mod.disable()
        if capture:
            jax.profiler.stop_trace()
    print(f"span cost, ring={ring} capture={capture}: {best * 1e6:.2f} us")
    assert best < cap_us * 1e-6, f"{best * 1e6:.2f} us a span"


def _profiled_server(tmp_path):
    """(engine, server, base url, calls): a toy engine behind the real
    server, its ProfileTrigger's start and stop injected to record
    (what, dir, the engine's tick count) in ``calls``."""
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
    cfg.logging.profile_dir = str(tmp_path)
    cfg.logging.profile_max_captures = 2
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer())
    assert engine.profile_trigger.out_dir == os.path.join(
        str(tmp_path), "ondemand")
    assert engine.profile_trigger.max_captures == 2
    calls = []
    engine.profile_trigger = ProfileTrigger(
        engine.profile_trigger.out_dir, max_captures=2,
        start_fn=lambda d: calls.append(("start", d, engine.ticks)),
        stop_fn=lambda: calls.append(("stop", None, engine.ticks)))
    srv = MegatronServer(engine)
    port = srv.start_background(port=0)
    return engine, srv, f"http://127.0.0.1:{port}", calls


def test_serving_profile_endpoint_brackets_ticks(tmp_path):
    """GET /profile?ticks=N on the serving port arms a capture that the
    engine's loop starts at its next step and stops N ticks later; the
    budget and the single-flight rule are ProfileTrigger's own."""
    from megatron_llm_tpu.generation.server import MegatronServer

    engine, srv, base, calls = _profiled_server(tmp_path)
    try:
        code, body, _ = _get(base + "/profile?ticks=zero")
        assert code == 400
        code, body, _ = _get(base + "/profile?ticks=2")
        assert code == 200 and json.loads(body)["steps"] == 2
        code, body, _ = _get(base + "/profile?ticks=2")
        assert code == 409  # one at a time
        assert calls == []  # idle: nothing starts until a step runs
        req = engine.submit([5, 6, 7], 5, use_eod_for_termination=False)
        req.result(timeout=120)
        (start, stop) = calls
        assert start[0] == "start" and "ondemand_000" in start[1]
        assert start[1].startswith(str(tmp_path))
        assert stop[0] == "stop" and stop[2] - start[2] == 2
    finally:
        srv.stop()
    # a legacy engine has no scheduler loop to bracket ticks: 503
    class _NoLoop:
        pass
    assert MegatronServer(_NoLoop()).profile({})[0] == 503


def test_serving_profile_window_is_clamped(tmp_path):
    """The endpoint is on the public port: no request holds a capture
    open for more than MAX_PROFILE_TICKS ticks."""
    from megatron_llm_tpu.generation import server as server_mod

    engine, srv, base, calls = _profiled_server(tmp_path)
    try:
        code, body, _ = _get(base + f"/profile?ticks={10 ** 12}")
        assert code == 200
        assert json.loads(body)["steps"] == server_mod.MAX_PROFILE_TICKS
        assert engine.profile_trigger.pending and calls == []
    finally:
        srv.stop()
    assert calls == []  # armed, never started: nothing to stop


def test_serving_profile_window_ends_when_engine_goes_idle(tmp_path):
    """A window longer than the traffic stops when the loop runs out of
    work, not N ticks into whatever comes next; the next request is free
    to arm another."""
    import time

    engine, srv, base, calls = _profiled_server(tmp_path)
    try:
        assert _get(base + "/profile?ticks=500")[0] == 200
        engine.submit([5, 6, 7], 3,
                      use_eod_for_termination=False).result(timeout=120)
        deadline = time.monotonic() + 30
        while len(calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        (start, stop) = calls
        assert start[0] == "start" and stop[0] == "stop"
        assert 1 <= stop[2] - start[2] < 500
        assert not engine.profile_trigger.active
        # the loop went back to its wait: a second window is accepted
        assert _get(base + "/profile?ticks=1")[0] == 200
        engine.submit([5, 6, 7], 2,
                      use_eod_for_termination=False).result(timeout=120)
        deadline = time.monotonic() + 30
        while len(calls) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert [c[0] for c in calls] == ["start", "stop"] * 2
        assert calls[3][2] - calls[2][2] == 1
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# (k) the compile log and the start-up phases (observability/compiles.py)
# ---------------------------------------------------------------------------


def _fed(marker):
    """The log's rows whose function name carries ``marker`` (the log is
    the process's: a test reads its own rows by name, not by position)."""
    from megatron_llm_tpu.observability import compiles

    return [r for r in compiles.log() if marker in r.fun_name]


def _region(event, seconds, fun_name, inside=()):
    """One timed region as jax fires it (``dispatch.log_elapsed_time``): a
    scalar as it opens, whatever happens ``inside``, its duration."""
    import jax.monitoring as mon

    mon.record_scalar(event, 0.0, fun_name=fun_name)
    for fire in inside:
        fire()
    mon.record_event_duration_secs(event, seconds, fun_name=fun_name)


def test_compile_log_rows_outcomes_and_counters():
    import time

    import jax.monitoring as mon

    from megatron_llm_tpu.observability import compiles as c

    c.install()
    reg = registry_mod.get_registry()
    names = ("compiles", "compile_seconds", "cache_hits", "cold_compiles",
             "cold_compile_seconds", "trace_seconds", "lower_seconds")
    read = lambda: {n: reg.counter(f"mlt_jit_{n}_total").value  # noqa: E731
                    for n in names}
    before, t_before = read(), time.monotonic()
    tracer = trace_mod.configure(capacity=256)
    try:
        _region(c.TRACE_EVENT, 0.5, "k1_f")
        # a helper traced inside the outer trace makes no row; a constant
        # compiled inside it makes its own and comes off the outer's seconds
        _region(c.TRACE_EVENT, 1.0, "k1_outer", inside=(
            lambda: _region(c.TRACE_EVENT, 0.25, "k1_where"),
            lambda: _region(c.COMPILE_EVENT, 0.125, "jit(k1_const)")))
        # an event with no opening scalar (fed by hand) is a region too
        mon.record_event_duration_secs(c.LOWER_EVENT, 0.25,
                                       fun_name="jit(k1_f)")
        _region(c.COMPILE_EVENT, 0.0625, "jit(k1_f)", inside=(
            lambda: mon.record_event(c.CACHE_HIT_EVENT),
            # the cache's own retrieval time: inside the row's seconds
            lambda: mon.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.03125)))
        _region(c.COMPILE_EVENT, 2.0, "jit(k1_f)")
        mon.record_event_duration_secs("/jax/some/other", 9.0, fun_name="k1_no")
        mon.record_event("/jax/compilation_cache/cache_misses")
    finally:
        trace_mod.disable()
    rows = _fed("k1_")
    assert [tuple(r[1:]) for r in rows] == [
        ("trace", "k1_f", 0.5, None),
        ("compile", "jit(k1_const)", 0.125, "cold"),
        ("trace", "k1_outer", 0.875, None),
        ("lower", "jit(k1_f)", 0.25, None),
        ("compile", "jit(k1_f)", 0.0625, "hit"),
        ("compile", "jit(k1_f)", 2.0, "cold")]
    stamps = [r.t_end for r in rows]
    assert stamps == sorted(stamps)
    assert t_before <= stamps[0] and stamps[-1] <= time.monotonic()
    assert c.installed_at() <= stamps[0]
    after = read()
    assert {n: after[n] - before[n] for n in names} == {
        "compiles": 3, "compile_seconds": 2.1875, "cache_hits": 1,
        "cold_compiles": 2,
        "cold_compile_seconds": 2.125, "trace_seconds": 1.375,
        "lower_seconds": 0.25}
    assert c.totals(rows) == {"compiles": 3, "hits": 1, "cold": 2,
                              "compile_s": 2.1875, "trace_s": 1.375,
                              "lower_s": 0.25}
    marks = [e[5] for e in tracer.snapshot() if e[1] == "jit-compile"]
    assert len(marks) == 6 and marks[4] == {
        "fun": "jit(k1_f)", "stage": "compile", "seconds": 0.0625,
        "outcome": "hit"}


def test_cache_hit_marks_its_own_threads_compile_only():
    import jax.monitoring as mon

    from megatron_llm_tpu.observability import compiles as c

    c.install()
    hit_fired, other_done = threading.Event(), threading.Event()

    def loads():       # thread A: a hit, then waits inside its region
        def wait():
            hit_fired.set()
            assert other_done.wait(timeout=30)
        _region(c.COMPILE_EVENT, 0.5, "jit(k2_a)", inside=(
            lambda: mon.record_event(c.CACHE_HIT_EVENT), wait))

    def compiles_cold():   # thread B: a whole compile meanwhile
        assert hit_fired.wait(timeout=30)
        _region(c.COMPILE_EVENT, 3.0, "jit(k2_b)")
        other_done.set()

    threads = [threading.Thread(target=f) for f in (loads, compiles_cold)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert [(r.fun_name, r.outcome) for r in _fed("k2_")] == [
        ("jit(k2_b)", "cold"), ("jit(k2_a)", "hit")]


def test_compile_log_is_bounded_and_a_second_install_does_not_double():
    import jax.monitoring as mon
    from jax._src import monitoring as mon_src

    from megatron_llm_tpu.observability import compiles as c

    c.install()
    at = c.installed_at()
    c.install()
    c.install_compile_counter()      # the name the server and trainer call
    assert c.installed_at() == at
    assert mon_src.get_event_duration_listeners().count(c._on_duration) == 1
    assert mon_src.get_event_listeners().count(c._on_event) == 1
    for i in range(c.LOG_ROWS + 7):
        mon.record_event_duration_secs(c.LOWER_EVENT, 0.001,
                                       fun_name=f"k3_{i}")
    log = c.log()
    assert len(log) == c.LOG_ROWS
    assert log[-1].fun_name == f"k3_{c.LOG_ROWS + 6}"
    assert log[0].fun_name == "k3_7"      # the oldest rows dropped
    log.clear()                           # a copy: the log keeps its rows
    assert len(c.log()) == c.LOG_ROWS


@pytest.mark.parametrize("way", ["environment", "cpu"])
def test_enable_compilation_cache_installs_the_log_first(way, monkeypatch):
    """Both early returns of ``enable_compilation_cache`` (a directory
    from the environment; a CPU backend, which keeps no cache) come after
    the installation."""
    from megatron_llm_tpu.observability import compiles as c
    from megatron_llm_tpu.utils import platform

    calls = []
    monkeypatch.setattr(c, "install", lambda: calls.append(way))
    if way == "environment":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nowhere/cache")
        assert platform.enable_compilation_cache() == "/nowhere/cache"
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert platform.enable_compilation_cache() is None
    assert calls == [way]


def test_persistent_cache_round_trip_reads_cold_then_hit(tmp_path):
    """A real program through a real cache directory on this backend:
    compiled, forgotten (``jax.clear_caches``), asked for again."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from megatron_llm_tpu.observability import compiles as c

    c.install()
    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in knobs}

    def k4_round_trip(x):
        return jnp.tanh(x) * 5.0 - 2.0

    a = np.ones((3, 17), np.float32)
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.jit(k4_round_trip)(a).block_until_ready()
        jax.clear_caches()
        jax.jit(k4_round_trip)(a).block_until_ready()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    got = [(r.stage, r.outcome) for r in _fed("k4_round_trip")]
    if not os.listdir(tmp_path):
        pytest.skip("this backend wrote no entry to the compilation cache")
    assert got == [("trace", None), ("lower", None), ("compile", "cold"),
                   ("trace", None), ("lower", None), ("compile", "hit")]


def test_startup_phase_gauge_row_and_ring_span():
    from megatron_llm_tpu.observability import compiles as c

    tracer = trace_mod.configure(capacity=64)
    try:
        with c.startup_phase("k5-phase", rows=3) as ph:
            pass

        @c.startup_phase("k5-whole-call")
        def build(x):
            return x + 1

        assert build(1) == 2 and build(2) == 3
    finally:
        trace_mod.disable()
    assert ph.t0 <= ph.t1
    mine = [p for p in c.phases() if p[0].startswith("k5-")]
    assert mine[0] == ("k5-phase", ph.t0, ph.t1, {"rows": 3})
    # as a decorator: a phase of its own for every call
    assert [p[0] for p in mine[1:]] == ["k5-whole-call"] * 2
    assert mine[1][2] <= mine[2][1]
    gauge = registry_mod.get_registry().gauge(
        "mlt_startup_phase_seconds", labels={"phase": "k5-phase"})
    assert gauge.value == ph.t1 - ph.t0
    spans = [e for e in tracer.snapshot() if e[0] == "X" and e[1] == "startup"]
    assert [e[5] for e in spans] == [
        {"phase": "k5-phase", "rows": 3}, {"phase": "k5-whole-call"},
        {"phase": "k5-whole-call"}]


def _summary_lines(text):
    return [l for l in text.splitlines() if "start-up: " in l]


def test_engine_says_its_start_up_once(capsys):
    import re

    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.observability import compiles as c
    from tests.test_generation import VOCAB, ToyTokenizer

    cfg = make_config(
        "llama2", num_layers=1, hidden_size=32, num_attention_heads=2,
        num_attention_heads_kv=2, ffn_hidden_size=64, seq_length=64,
        max_position_embeddings=128, vocab_size=VOCAB,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                      max_slots=2, max_seq=64)
    for prompt in ([5, 6, 7], [8, 9]):
        engine.submit(prompt, 3, use_eod_for_termination=False)
        engine.run_until_idle()
    assert engine.ticks > 2
    (line,) = _summary_lines(capsys.readouterr().out)
    assert line.startswith("[engine] start-up: ")
    names = [p[0] for p in c.phases()]     # the process's: read the tail
    last_build = len(names) - 1 - names[::-1].index("engine-build")
    assert "tick-program" in names[last_build + 1:]
    assert "engine-build" in line and "tick-program rows=" in line
    m = re.search(r"compiles (\d+) \((\d+) hit, (\d+) cold\) [\d.]+ s, "
                  r"trace [\d.]+ s, lower [\d.]+ s, in the log's first", line)
    assert m and int(m.group(1)) == int(m.group(2)) + int(m.group(3))


def test_pretrain_says_its_start_up_once(capsys):
    from megatron_llm_tpu.observability import compiles as c
    from megatron_llm_tpu.training import pretrain

    result = pretrain(_tiny_cfg(train_iters=2),
                      data_iterators_provider=_provider())
    assert result["iteration"] == 2
    (line,) = _summary_lines(capsys.readouterr().out)
    said = line.split(";")[0]
    order = [said.index(p) for p in ("mesh ", "model-setup ", "first-step ")]
    assert order == sorted(order) and "checkpoint-load" not in said
    # one pair of clock stamps: the phase's seconds ARE the warm-up time
    first = [p for p in c.phases() if p[0] == "first-step"][-1]
    assert result["warmup_time"] == first[2] - first[1]
    assert registry_mod.get_registry().gauge(
        "mlt_startup_phase_seconds",
        labels={"phase": "first-step"}).value == result["warmup_time"]
