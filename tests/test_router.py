"""Cross-replica router tests (serving/router/, ISSUE 10).

Four layers, mirroring the subsystem: policy decisions against synthetic
ReplicaViews, the circuit-breaker state machine, the forwarding proxy's
retry/failover/partial-stream semantics against programmable fake
replicas, and an end-to-end 2-replica loopback fleet asserting routed
responses are token-identical to hitting a replica directly.
"""

import dataclasses
import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from megatron_llm_tpu.serving.router import (
    DRAINING,
    EJECTED,
    HEALTHY,
    SUSPECT,
    DisaggPolicy,
    FleetOverloaded,
    ForwardingProxy,
    HealthPoller,
    LeastLoadedPolicy,
    PrefixAffinityPolicy,
    Replica,
    ReplicaRegistry,
    ReplicaView,
    RoundRobinPolicy,
    RouteRequest,
    SloAwarePolicy,
)
from megatron_llm_tpu.serving.router.server import RouterServer


def _view(url, *, replica_id=None, seq=1, queued=0, active=0, slots=4,
          ema_retire_ms=None, ema_tick_ms=None, retry_after_s=None,
          fetched_at=None, **extra):
    payload = {
        "replica_id": replica_id or url, "seq": seq, "uptime_s": 1.0,
        "active_slots": active, "max_slots": slots, "queued": queued,
        "scheduler": {"policy": "fcfs", "retry_after_s": retry_after_s,
                      "ema_retire_ms": ema_retire_ms,
                      "ema_tick_ms": ema_tick_ms},
        **extra,
    }
    v = ReplicaView.parse(url, payload)
    if fetched_at is not None:
        v = dataclasses.replace(v, fetched_at=fetched_at)
    return v


REQ = RouteRequest(prefix_text="shared system prompt " * 8)


# ---------------------------------------------------------------------------
# Policy decision matrix
# ---------------------------------------------------------------------------


def test_round_robin_cycles_in_fleet_order():
    views = [_view(f"http://r{i}") for i in range(3)]
    pol = RoundRobinPolicy()
    firsts = [pol.order(REQ, views)[0].url for _ in range(6)]
    assert firsts == ["http://r0", "http://r1", "http://r2"] * 2
    # every order is a permutation of the whole fleet (failover candidates)
    assert sorted(v.url for v in pol.order(REQ, views)) == \
        sorted(v.url for v in views)


def test_least_loaded_scores_depth_times_drain_ema():
    # r0: 6 deep but drains 10ms/req -> 0.06s; r1: 2 deep at 100ms -> 0.2s
    views = [_view("http://r0", queued=4, active=2, ema_retire_ms=10.0),
             _view("http://r1", queued=1, active=1, ema_retire_ms=100.0)]
    assert LeastLoadedPolicy().order(REQ, views)[0].url == "http://r0"
    # raw queue depth would have picked r1 — the drain EMA is load-bearing
    assert views[0].depth > views[1].depth


def test_least_loaded_without_timing_falls_back_to_depth():
    views = [_view("http://r0", queued=3), _view("http://r1", queued=1)]
    assert LeastLoadedPolicy().order(REQ, views)[0].url == "http://r1"


def test_least_loaded_ties_break_on_kv_byte_headroom():
    """Mixed-dtype fleets compare BYTE headroom, not page counts: an
    int8 replica's free page holds half a bf16 replica's (ISSUE 13/19).
    Here the int8 replica has MORE free pages but FEWER free bytes."""
    bf16 = _view("http://bf16", free_pages=10, total_pages=20,
                 kv_pool_bytes=40 << 20)   # 2 MB/page -> 20 MB free
    int8 = _view("http://int8", free_pages=15, total_pages=20,
                 kv_pool_bytes=20 << 20)   # 1 MB/page -> 15 MB free
    order = LeastLoadedPolicy().order(REQ, [int8, bf16])
    assert [v.url for v in order] == ["http://bf16", "http://int8"]
    # replicas predating the byte budget tie-break on raw page counts
    old = [_view("http://a", free_pages=3), _view("http://b", free_pages=9)]
    assert LeastLoadedPolicy().order(REQ, old)[0].url == "http://b"


def test_disagg_orders_decode_then_unified_then_prefill():
    views = [_view("http://p", role="prefill"), _view("http://u"),
             _view("http://d", role="decode")]
    assert [v.url for v in DisaggPolicy().order(REQ, views)] == \
        ["http://d", "http://u", "http://p"]


def test_disagg_degrades_to_least_loaded_on_roleless_fleet():
    views = [_view("http://r0", queued=3), _view("http://r1", queued=1)]
    assert [v.url for v in DisaggPolicy().order(REQ, views)] == \
        ["http://r1", "http://r0"]


def test_disagg_prefill_candidates_gates():
    """The prefill hop is spent only on single-prompt, non-logprobs
    requests past the length threshold, and only when the fleet holds
    BOTH roles — every other shape routes exactly like least_loaded."""
    pol = DisaggPolicy(long_prompt_chars=64)
    long_req = RouteRequest(prefix_text="x" * 100)
    pre = _view("http://p", role="prefill")
    dec = _view("http://d", role="decode")
    assert [v.url for v in pol.prefill_candidates(long_req, [pre, dec])] \
        == ["http://p"]
    assert pol.prefill_candidates(
        RouteRequest(prefix_text="short"), [pre, dec]) == []
    assert pol.prefill_candidates(
        RouteRequest(prefix_text="x" * 100, logprobs=True),
        [pre, dec]) == []
    assert pol.prefill_candidates(
        RouteRequest(prefix_text="x" * 100, n_prompts=2), [pre, dec]) == []
    assert pol.prefill_candidates(long_req, [pre, _view("http://u")]) == []
    assert pol.prefill_candidates(long_req, [dec, _view("http://u")]) == []


def test_prefix_affinity_is_stable_and_order_independent():
    views = [_view(f"http://r{i}", replica_id=f"id{i}") for i in range(4)]
    pol = PrefixAffinityPolicy()
    chosen = pol.order(REQ, views)[0].url
    # stable across calls AND across fleet-list permutations (consistent
    # hashing on replica_id, not list position)
    assert pol.order(REQ, views)[0].url == chosen
    assert pol.order(REQ, list(reversed(views)))[0].url == chosen


def test_prefix_affinity_spreads_distinct_prefixes():
    views = [_view(f"http://r{i}", replica_id=f"id{i}") for i in range(4)]
    pol = PrefixAffinityPolicy()
    targets = {pol.order(RouteRequest(prefix_text=f"prompt family {i} " * 9),
                         views)[0].url for i in range(32)}
    assert len(targets) >= 2, "32 distinct prefixes all hashed to one replica"


def test_prefix_affinity_key_horizon_ignores_tails():
    views = [_view(f"http://r{i}", replica_id=f"id{i}") for i in range(4)]
    pol = PrefixAffinityPolicy(prefix_chars=64)
    shared = "x" * 64
    urls = {pol.order(RouteRequest(prefix_text=shared + tail), views)[0].url
            for tail in ("", "A" * 100, "B" * 500)}
    assert len(urls) == 1, "tails beyond the key horizon changed the route"


def test_prefix_affinity_bounded_load_spills_hot_replica():
    views = [_view(f"http://r{i}", replica_id=f"id{i}") for i in range(3)]
    pol = PrefixAffinityPolicy()
    hot_url = pol.order(REQ, views)[0].url
    # pile a backlog onto the ring choice; everyone else is idle
    loaded = [_view(v.url, replica_id=v.replica_id,
                    queued=8 if v.url == hot_url else 0,
                    active=4 if v.url == hot_url else 0)
              for v in views]
    order = pol.order(REQ, loaded)
    assert order[0].url != hot_url, "hot prefix did not spill"
    assert order[1].url == hot_url, "ring choice should stay second"


def test_prefix_affinity_no_spill_below_bound():
    views = [_view(f"http://r{i}", replica_id=f"id{i}") for i in range(3)]
    pol = PrefixAffinityPolicy()
    hot_url = pol.order(REQ, views)[0].url
    # one queued request is within min_headroom of the idle mean: no spill
    loaded = [_view(v.url, replica_id=v.replica_id,
                    queued=1 if v.url == hot_url else 0) for v in views]
    assert pol.order(REQ, loaded)[0].url == hot_url


def test_slo_aware_picks_fastest_feasible():
    views = [_view("http://slow", queued=8, active=4, ema_retire_ms=500.0,
                   retry_after_s=4.0),
             _view("http://fast", queued=0, active=1, ema_tick_ms=20.0)]
    req = RouteRequest(prefix_text="x", ttft_deadline_ms=500.0)
    order = SloAwarePolicy().order(req, views)
    assert order[0].url == "http://fast"
    assert [v.url for v in order] == ["http://fast", "http://slow"]


def test_slo_aware_sheds_with_fleet_min_retry_after():
    views = [_view("http://a", queued=8, active=4, retry_after_s=9.0),
             _view("http://b", queued=8, active=4, retry_after_s=3.0)]
    req = RouteRequest(prefix_text="x", ttft_deadline_ms=100.0)
    with pytest.raises(FleetOverloaded) as ei:
        SloAwarePolicy().order(req, views)
    # the aggregated 503's Retry-After is the SOONEST replica's estimate
    assert ei.value.retry_after == pytest.approx(3.0)
    assert set(ei.value.info["predicted_wait_s"]) == {"http://a", "http://b"}


def test_slo_aware_without_deadline_degrades_to_least_loaded():
    views = [_view("http://a", queued=5, ema_retire_ms=100.0),
             _view("http://b", queued=1, ema_retire_ms=100.0)]
    req = RouteRequest(prefix_text="x")
    assert SloAwarePolicy().order(req, views)[0].url == "http://b"


# ---------------------------------------------------------------------------
# Circuit-breaker state machine
# ---------------------------------------------------------------------------


def test_breaker_failure_ladder_and_recovery():
    rep = Replica("http://r", suspect_after=1, eject_after=3)
    rep.record_view(_view("http://r"))
    assert rep.state == HEALTHY and rep.routable(None)
    assert rep.record_failure("boom") == SUSPECT
    assert rep.routable(None), "suspect replicas still route"
    rep.record_failure("boom")
    assert rep.state == SUSPECT
    assert rep.record_failure("boom") == EJECTED
    assert not rep.routable(None)
    # recovery probe succeeds -> straight back to healthy, failures reset
    rep.record_view(_view("http://r", seq=2))
    assert rep.state == HEALTHY and rep.summary()["consecutive_failures"] == 0


def test_breaker_drain_is_operator_sticky():
    rep = Replica("http://r")
    rep.record_view(_view("http://r"))
    rep.drain(True)
    assert rep.state == DRAINING and not rep.routable(None)
    # successful polls keep refreshing the view but cannot undrain
    rep.record_view(_view("http://r", seq=2))
    assert rep.state == DRAINING
    # failures while draining don't flap the state either
    rep.record_failure("boom")
    assert rep.state == DRAINING
    rep.drain(False)
    # undrain re-enters through the breaker using the failure count
    assert rep.state == SUSPECT
    rep.record_view(_view("http://r", seq=3))
    assert rep.state == HEALTHY


def test_breaker_detects_restart_by_replica_id():
    rep = Replica("http://r")
    rep.record_view(_view("http://r", replica_id="proc1", seq=100))
    # same url, new process: fresh id, seq starts over — accepted
    assert rep.record_view(_view("http://r", replica_id="proc2", seq=1))
    s = rep.summary()
    assert s["restarts"] == 1 and s["seq"] == 1


def test_breaker_discards_stale_and_reordered_payloads():
    rep = Replica("http://r")
    rep.record_view(_view("http://r", replica_id="p", seq=5, queued=7))
    assert not rep.record_view(_view("http://r", replica_id="p", seq=4,
                                     queued=0)), "older seq must not apply"
    assert not rep.record_view(_view("http://r", replica_id="p", seq=5))
    assert rep.view.queued == 7
    assert rep.summary()["stale_discards"] == 2
    assert rep.record_view(_view("http://r", replica_id="p", seq=6))


def test_staleness_gates_routability():
    rep = Replica("http://r")
    old = time.monotonic() - 99.0
    rep.record_view(_view("http://r", fetched_at=old))
    assert rep.routable(None), "no staleness bound -> any view routes"
    assert not rep.routable(10.0), "stale view must not route"


def test_poller_drives_breaker_and_registry_views():
    calls = {"n": 0}

    def fetch(url, timeout):
        calls["n"] += 1
        if calls["n"] <= 3:
            raise ConnectionError("down")
        return {"replica_id": "p", "seq": calls["n"], "active_slots": 1,
                "max_slots": 4}

    registry = ReplicaRegistry(["http://r"], eject_after=3)
    poller = HealthPoller(registry, fetch=fetch)
    rep = registry.get("http://r")
    for expect in (SUSPECT, SUSPECT, EJECTED):
        assert not poller.poll_once(rep)
        assert rep.state == expect
    assert registry.routable_views() == []
    assert poller.poll_once(rep)  # recovery probe
    assert rep.state == HEALTHY
    assert [v.url for v in registry.routable_views()] == ["http://r"]


# ---------------------------------------------------------------------------
# Forwarding proxy semantics (programmable fake replicas)
# ---------------------------------------------------------------------------


class _FakeReplica:
    """Minimal /api + /health replica with a programmable script.

    ``script`` entries per request: ("ok", body) | ("503", retry_after)
    | ("partial",).  Past the script's end it keeps answering "ok"."""

    def __init__(self, script=()):
        self.script = list(script)
        self.requests = 0
        self.health_polls = 0
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_PUT(self):
                outer.requests += 1
                step = (outer.script[outer.requests - 1]
                        if outer.requests <= len(outer.script) else ("ok",))
                if step[0] == "503":
                    body = json.dumps({"error": "queue full",
                                       "retry_after": step[1]}).encode()
                    self.send_response(503)
                    self.send_header("Retry-After",
                                     str(max(1, int(step[1]))))
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if step[0] == "partial":
                    # promise 1000 bytes, deliver 10, FIN: response-phase
                    # failure after the request was accepted
                    self.send_response(200)
                    self.send_header("Content-Length", "1000")
                    self.end_headers()
                    self.wfile.write(b'{"text": [')
                    self.wfile.flush()
                    self.connection.shutdown(socket.SHUT_WR)
                    return
                body = json.dumps({"text": ["ok"], "served_by": outer.url,
                                   "n": outer.requests}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                outer.health_polls += 1
                body = json.dumps({
                    "status": "ok", "replica_id": outer.url,
                    "seq": outer.health_polls, "uptime_s": 1.0,
                    "active_slots": 0, "max_slots": 4, "queued": 0,
                }).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _dead_url():
    """A url nothing listens on (bind, grab the port, close)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


BODY = json.dumps({"prompts": ["hi"], "tokens_to_generate": 1}).encode()


def test_proxy_failover_excludes_connect_failed_replica():
    dead = _dead_url()
    live = _FakeReplica()
    try:
        registry = ReplicaRegistry([dead, live.url])
        out = ForwardingProxy(registry, timeout_s=5.0).forward(
            [dead, live.url], BODY)
        assert out.status == 200
        assert json.loads(out.body)["served_by"] == live.url
        assert out.failovers == 1 and out.retries == 0
        # the data-plane failure fed the breaker without waiting for a poll
        assert registry.get(dead).state == SUSPECT
    finally:
        live.stop()


def test_proxy_honors_retry_after_then_succeeds():
    rep = _FakeReplica(script=[("503", 2.0), ("ok",)])
    slept = []
    try:
        registry = ReplicaRegistry([rep.url])
        proxy = ForwardingProxy(registry, timeout_s=5.0,
                                sleep=slept.append)
        out = proxy.forward([rep.url], BODY)
        assert out.status == 200 and out.retries == 1
        assert slept == [2.0], "must sleep the replica's Retry-After"
    finally:
        rep.stop()


def test_proxy_bounded_retries_then_aggregated_503():
    rep = _FakeReplica(script=[("503", 2.0)] * 10)
    slept = []
    try:
        registry = ReplicaRegistry([rep.url])
        proxy = ForwardingProxy(registry, timeout_s=5.0, max_retries=2,
                                sleep=slept.append)
        out = proxy.forward([rep.url], BODY)
        assert out.status == 503
        assert rep.requests == 3, "1 walk + max_retries rounds, no more"
        body = json.loads(out.body)
        assert body["fleet_saturated"] is True
        assert out.retry_after == pytest.approx(2.0)
    finally:
        rep.stop()


def test_proxy_backoff_cap_bounds_long_retry_after():
    rep = _FakeReplica(script=[("503", 60.0), ("ok",)])
    slept = []
    try:
        proxy = ForwardingProxy(ReplicaRegistry([rep.url]), timeout_s=5.0,
                                backoff_cap_s=0.05, sleep=slept.append)
        assert proxy.forward([rep.url], BODY).status == 200
        assert slept == [0.05]
    finally:
        rep.stop()


def test_proxy_never_retries_partial_response():
    """A response that dies mid-body is non-idempotent: exactly one
    upstream request, a structured 502, no failover to the healthy twin."""
    partial = _FakeReplica(script=[("partial",)])
    healthy = _FakeReplica()
    try:
        registry = ReplicaRegistry([partial.url, healthy.url])
        out = ForwardingProxy(registry, timeout_s=5.0).forward(
            [partial.url, healthy.url], BODY)
        assert out.status == 502
        assert b"not retried" in out.body
        assert partial.requests == 1
        assert healthy.requests == 0, "partial stream must not fail over"
    finally:
        partial.stop()
        healthy.stop()


def test_proxy_forwards_4xx_verbatim_without_failover():
    rep = _FakeReplica()
    other = _FakeReplica()

    # patch the first replica to 400 every request
    def do_put(handler):
        rep.requests += 1
        body = json.dumps({"error": "prompts is empty"}).encode()
        handler.send_response(400)
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)

    rep.httpd.RequestHandlerClass.do_PUT = do_put
    try:
        out = ForwardingProxy(
            ReplicaRegistry([rep.url, other.url]), timeout_s=5.0
        ).forward([rep.url, other.url], BODY)
        assert out.status == 400
        assert other.requests == 0, "client errors are terminal fleet-wide"
    finally:
        rep.stop()
        other.stop()


# ---------------------------------------------------------------------------
# RouterServer endpoints against fake replicas
# ---------------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read()


def _put(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="PUT")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_router_server_routes_health_metrics_and_drain():
    reps = [_FakeReplica(), _FakeReplica()]
    router = RouterServer([r.url for r in reps], policy="round_robin",
                          poll_interval=30.0)  # warm poll only
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"

        # routing: round_robin alternates replicas
        served = [_put(base + "/api", {"prompts": ["hi"],
                                       "tokens_to_generate": 1})[1]
                  ["served_by"] for _ in range(4)]
        assert served[0] != served[1] and served[:2] == served[2:]

        # fleet /health summary
        status, body = _get(base + "/health")
        info = json.loads(body)
        assert info["role"] == "router" and info["policy"] == "round_robin"
        assert info["routable"] == 2 and len(info["replicas"]) == 2
        assert all(r["state"] == HEALTHY for r in info["replicas"])
        assert all(r["replica_id"] for r in info["replicas"])

        # /metrics exposition
        status, body = _get(base + "/metrics")
        text = body.decode()
        assert "mlt_router_replica_up" in text
        assert "mlt_router_decisions_total" in text
        assert "mlt_router_ttft_seconds_bucket" in text

        # operator drain: no new traffic to the drained replica
        target = reps[0].url
        req = urllib.request.Request(
            base + "/admin/drain",
            data=json.dumps({"replica": target}).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["state"] == DRAINING
        before = reps[0].requests
        for _ in range(4):
            code, body = _put(base + "/api", {"prompts": ["hi"],
                                              "tokens_to_generate": 1})
            assert code == 200 and body["served_by"] != target
        assert reps[0].requests == before

        # undrain restores it
        req = urllib.request.Request(
            base + "/admin/undrain",
            data=json.dumps({"replica": target}).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["state"] == HEALTHY
    finally:
        router.stop()
        for r in reps:
            r.stop()


def test_router_server_503_when_no_replica_routable():
    dead = _dead_url()
    router = RouterServer([dead], poll_interval=30.0)
    try:
        port = router.start_background()
        code, body = _put(f"http://127.0.0.1:{port}/api",
                          {"prompts": ["hi"], "tokens_to_generate": 1})
        assert code == 503 and "no routable replica" in body["error"]
        assert body["retry_after"] >= 1.0
    finally:
        router.stop()


def test_router_server_slo_shed_is_structured_503():
    rep = _FakeReplica()
    try:
        router = RouterServer([rep.url], policy="slo_aware",
                              poll_interval=30.0)
        port = router.start_background()
        # poison the view with a hopeless backlog, then ask for 1ms TTFT
        router.registry.get(rep.url).record_view(
            _view(rep.url, seq=999, queued=50, active=4, retry_after_s=8.0))
        code, body = _put(f"http://127.0.0.1:{port}/api",
                          {"prompts": ["hi"], "tokens_to_generate": 1,
                           "ttft_deadline_ms": 1.0})
        assert code == 503 and body["shed"] is True
        assert body["retry_after"] >= 1.0
        assert rep.requests == 0, "shed requests must not reach replicas"
    finally:
        router.stop()
        rep.stop()


# ---------------------------------------------------------------------------
# End-to-end: 2-replica loopback fleet over real engines
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    """Two continuous-batching replicas sharing identical weights, behind
    real MegatronServers on ephemeral ports (--port 0 semantics)."""
    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.models import init_model_params, make_config
    from tests.test_generation import VOCAB, ToyTokenizer

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    servers, urls = [], []
    for _ in range(2):
        engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                          max_slots=4, max_seq=128)
        srv = MegatronServer(engine)
        port = srv.start_background(port=0)
        servers.append(srv)
        urls.append(f"http://127.0.0.1:{port}")
    yield servers, urls
    for srv in servers:
        try:
            srv.stop()
        except Exception:
            pass


def test_replica_health_carries_router_identity_fields(fleet):
    """ISSUE 10 satellite: /health gains replica_id (stable per process),
    seq (monotonic), uptime_s, page_size."""
    _, urls = fleet
    _, b1 = _get(urls[0] + "/health")
    _, b2 = _get(urls[0] + "/health")
    h1, h2 = json.loads(b1), json.loads(b2)
    for field in ("replica_id", "seq", "uptime_s", "page_size"):
        assert field in h1, f"missing {field}"
    assert h2["replica_id"] == h1["replica_id"]
    assert h2["seq"] > h1["seq"], "seq must be monotonic"
    assert h2["uptime_s"] >= h1["uptime_s"]
    # distinct processes (here: distinct servers) get distinct ids
    _, bo = _get(urls[1] + "/health")
    assert json.loads(bo)["replica_id"] != h1["replica_id"]


GEN = dict(tokens_to_generate=12, top_k=1, logprobs=True)


def test_e2e_routed_responses_token_identical_to_direct(fleet):
    """The acceptance bar: the same greedy request through the router and
    straight at a replica produces identical text/segments/logprobs."""
    _, urls = fleet
    router = RouterServer(urls, policy="round_robin", poll_interval=30.0)
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"
        for i in range(4):  # alternates replicas under round_robin
            payload = {"prompts": [f"route me {i} please"], **GEN}
            code, routed = _put(base + "/api", payload)
            assert code == 200
            direct = [_put(u + "/api", payload)[1] for u in urls]
            # the timing block (ISSUE 12) is per-serve metadata — wall
            # clocks and trace ids legitimately differ per request; the
            # generation payload must not
            for b in (routed, *direct):
                assert b.pop("timing", None) is not None
            assert routed == direct[0] == direct[1], (
                "routing changed the tokens")
    finally:
        router.stop()


def test_e2e_failover_mid_fleet_zero_dropped(fleet):
    """Kill one replica (listening socket down — new connections refused),
    then push traffic: every request succeeds via failover, the breaker
    ejects the dead replica, and answers stay token-identical."""
    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
    from megatron_llm_tpu.generation.server import MegatronServer
    from tests.test_generation import ToyTokenizer

    servers, urls = fleet
    # a sacrificial third replica so the module fleet survives this test
    eng = servers[0].engine
    victim_srv = MegatronServer(ContinuousBatchingEngine(
        eng.cfg, eng.params, ToyTokenizer(), max_slots=4, max_seq=128))
    vport = victim_srv.start_background(port=0)
    victim = f"http://127.0.0.1:{vport}"
    router = RouterServer([victim, urls[0]], policy="round_robin",
                          poll_interval=30.0, eject_after=2)
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"
        payload = {"prompts": ["failover determinism probe"], **GEN}
        code, before = _put(base + "/api", payload)
        assert code == 200
        before.pop("timing", None)  # per-serve metadata (ISSUE 12)
        victim_srv.stop()  # refuse new connections from here on
        results = [None] * 6

        def worker(i):
            code_i, body_i = _put(base + "/api", payload)
            if isinstance(body_i, dict):
                body_i.pop("timing", None)  # per-serve metadata
            results[i] = (code_i, body_i)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(results))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(code == 200 for code, _ in results), (
            f"dropped requests during failover: "
            f"{[c for c, _ in results if c != 200]}")
        assert all(body == before for _, body in results), (
            "failover changed the tokens")
        assert router.registry.get(victim).state == EJECTED
        assert router.registry.get(urls[0]).state == HEALTHY
    finally:
        router.stop()


def test_e2e_prefix_affinity_colocates_shared_prefix(fleet):
    """Requests sharing a system prompt all land on one replica (the other
    replica's engine never ticks), and that replica's prefix cache serves
    the shared pages."""
    servers, urls = fleet
    router = RouterServer(urls, policy="prefix_affinity",
                          policy_kwargs=dict(prefix_chars=64),
                          poll_interval=30.0)
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"
        shared = "fleet shared system prompt " * 4  # > prefix_chars horizon
        engines = [s.engine for s in servers]
        ticks0 = [e.ticks for e in engines]
        hits0 = [e.prefix_hit_tokens for e in engines]
        for i in range(5):
            # logprobs requests skip prefix matching by design (PR 5), so
            # this workload decodes plain greedy
            code, _ = _put(base + "/api",
                           {"prompts": [shared + f" tail {i}"],
                            "tokens_to_generate": 12, "top_k": 1})
            assert code == 200
        ticked = [e.ticks - t0 for e, t0 in zip(engines, ticks0)]
        assert sorted(ticked)[0] == 0, (
            f"shared-prefix traffic split across replicas: {ticked}")
        hit_gain = [e.prefix_hit_tokens - h0
                    for e, h0 in zip(engines, hits0)]
        assert max(hit_gain) > 0, "co-located requests never hit the cache"
    finally:
        router.stop()


def test_server_tool_port_zero_prints_bound_port():
    """ISSUE 10 satellite: ``run_text_generation_server.py --port 0``
    binds an ephemeral port and prints it on startup — the fleet-spawning
    contract (parse the line, then poll /health)."""
    import os
    import re
    import signal
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "tools/run_text_generation_server.py",
         "--random_init", "--port", "0", "--host", "127.0.0.1",
         "--tokenizer_type", "NullTokenizer", "--vocab_size", "128",
         "--num_layers", "1", "--hidden_size", "32",
         "--num_attention_heads", "2", "--ffn_hidden_size", "64",
         "--seq_length", "64", "--max_position_embeddings", "64"],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        port = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            m = re.search(r"on http://127\.0\.0\.1:(\d+)/api", line)
            if m:
                port = int(m.group(1))
                break
        assert port is not None, "server never printed its bound port"
        assert port != 0
        _, body = _get(f"http://127.0.0.1:{port}/health")
        info = json.loads(body)
        assert info["status"] == "ok"
        assert info["replica_id"] and info["seq"] >= 1
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)


def test_run_router_tool_parses_and_requires_replicas():
    """tools/run_router.py wires flags to the server (no sockets here —
    argparse-level contract)."""
    import tools.run_router as rr

    with pytest.raises(SystemExit):
        rr.main(["--policy", "least_loaded"])  # no replicas
    with pytest.raises(SystemExit):
        rr.main(["--replica", "http://x", "--policy", "nonsense"])
