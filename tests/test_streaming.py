"""Streaming serving tier tests (serving/streaming/, ISSUE 18).

Four layers, mirroring the subsystem: the bounded per-request emission
queue and SSE wire helpers in isolation, the engine's submit_stream
path against the buffered path (token identity), a real 2-replica
loopback fleet streaming end-to-end through the router (byte/token
identity, trace propagation, mid-stream death semantics), and the
elastic-discovery + admission-queue control plane.
"""

import http.client
import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlparse

import pytest

from megatron_llm_tpu.serving.router.admission import (
    AdmissionOverflow,
    AdmissionQueue,
)
from megatron_llm_tpu.serving.streaming import (
    StreamEvent,
    StreamQueue,
    parse_sse,
    sse_encode,
    sse_scan_terminal,
)

# ---------------------------------------------------------------------------
# StreamQueue: bounded emission with honest drop-to-terminal semantics
# ---------------------------------------------------------------------------


def test_stream_queue_orders_tokens_then_terminal_exactly_once():
    q = StreamQueue(maxsize=8)
    assert q.publish_tokens([1, 2], [0.1, 0.2]) == 0
    assert q.publish_tokens([3], [0.3]) == 0
    q.publish_terminal(StreamEvent("done", data={"outcome": "ok"}))
    evs = list(q.iter_events(timeout=1.0))
    assert [e.kind for e in evs] == ["token", "token", "done"]
    assert evs[0].tokens == [1, 2] and evs[1].tokens == [3]
    # terminal is delivered exactly once; afterwards the queue is dry
    assert q.next_event(timeout=0.01) is None


def test_stream_queue_overflow_drops_with_honest_count():
    q = StreamQueue(maxsize=2)
    shed = sum(q.publish_tokens([i]) for i in range(5))
    assert shed == 3 and q.dropped == 3, "publish must never block"
    q.publish_terminal(StreamEvent("done", data={}))
    evs = list(q.iter_events(timeout=1.0))
    # the queued incrementals survive, the terminal carries the count
    assert [e.kind for e in evs] == ["token", "token", "done"]
    assert evs[-1].data["dropped_events"] == 3


def test_stream_queue_abandon_sheds_future_publishes():
    q = StreamQueue(maxsize=8)
    q.publish_tokens([1])
    q.abandon()
    assert q.publish_tokens([2]) == 1, "post-abandon publishes are shed"
    q.publish_terminal(StreamEvent("done", data={}))
    assert q.next_event(timeout=0.01) is None, "abandoned consumers get nothing"


def test_stream_queue_first_terminal_wins():
    q = StreamQueue(maxsize=8)
    q.publish_terminal(StreamEvent("error", data={"error": "boom"}))
    q.publish_terminal(StreamEvent("done", data={}))
    evs = list(q.iter_events(timeout=1.0))
    assert [e.kind for e in evs] == ["error"]


# ---------------------------------------------------------------------------
# SSE wire helpers
# ---------------------------------------------------------------------------


def test_sse_encode_parse_roundtrip():
    raw = (sse_encode("token", {"tokens": [1, 2]})
           + sse_encode("done", {"text": ["hi\nthere"]}))
    events = parse_sse(raw)
    assert [e for e, _ in events] == ["token", "done"]
    assert events[0][1]["tokens"] == [1, 2]
    assert events[1][1]["text"] == ["hi\nthere"]


def test_sse_terminal_scan_across_chunk_boundaries():
    raw = sse_encode("token", {"t": 1}) + sse_encode("done", {"ok": 1})
    for cut in range(1, len(raw)):
        tail, seen = b"\n", False
        for chunk in (raw[:cut], raw[cut:]):
            seen, tail = sse_scan_terminal(tail, chunk)
            if seen:
                break
        assert seen, f"terminal frame missed when split at byte {cut}"
    # a stream with no terminal frame must never scan as terminated
    seen, _ = sse_scan_terminal(b"\n", sse_encode("token", {"t": 1}))
    assert not seen


# ---------------------------------------------------------------------------
# AdmissionQueue
# ---------------------------------------------------------------------------


def test_admission_fifo_grant_and_overflow():
    adm = AdmissionQueue(limit=1, depth=2, timeout_s=5.0)
    assert adm.try_admit() == 0.0  # fast path
    order = []

    def waiter(tag):
        if adm.try_admit() is not None:
            order.append(tag)

    t1 = threading.Thread(target=waiter, args=("first",))
    t1.start()
    while adm.queued() < 1:
        time.sleep(0.005)
    t2 = threading.Thread(target=waiter, args=("second",))
    t2.start()
    while adm.queued() < 2:
        time.sleep(0.005)
    with pytest.raises(AdmissionOverflow):
        adm.try_admit()  # bounded queue full -> immediate 503 material
    adm.release()
    t1.join(timeout=5)
    adm.release()
    t2.join(timeout=5)
    assert order == ["first", "second"], "grants must be strict FIFO"
    adm.release()
    assert adm.stats()["inflight"] == 0 and adm.stats()["overflows"] == 1


def test_admission_deadline_timeout_returns_none():
    adm = AdmissionQueue(limit=1, depth=4, timeout_s=5.0)
    assert adm.try_admit() == 0.0
    t0 = time.monotonic()
    assert adm.try_admit(deadline_s=0.05) is None, "saturated past deadline"
    assert time.monotonic() - t0 < 2.0
    assert adm.stats()["timeouts"] == 1 and adm.queued() == 0
    adm.release()
    assert adm.try_admit() == 0.0, "timed-out waiter must not leak a slot"


# ---------------------------------------------------------------------------
# Real-engine fleet (module scope: weights shared across tests)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    """Two continuous-batching replicas with identical weights behind real
    MegatronServers, mirroring tests/test_router.py's fixture."""
    from megatron_llm_tpu.generation.server import MegatronServer

    servers, urls = [], []
    for _ in range(2):
        engine = _toy_engine(max_slots=4)
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


def _put(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="PUT")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _stream_put(base, payload, headers=None, timeout=120):
    """PUT with incremental SSE reads; returns (status, headers, frames,
    first_frame_latency_s) where frames is parse_sse's [(event, data)]."""
    u = urlparse(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    t0 = time.monotonic()
    conn.request("PUT", "/api", body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json",
                          **(headers or {})})
    resp = conn.getresponse()
    hdrs = dict(resp.getheaders())
    raw, t_first = b"", None
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        if t_first is None:
            t_first = time.monotonic() - t0
        raw += chunk
    conn.close()
    if resp.status != 200 or not hdrs.get(
            "Content-Type", "").startswith("text/event-stream"):
        return resp.status, hdrs, json.loads(raw), t_first
    return resp.status, hdrs, parse_sse(raw), t_first


GEN = dict(tokens_to_generate=12, top_k=1, logprobs=True, random_seed=7)


def test_engine_stream_tokens_match_buffered_submit(fleet):
    """submit_stream's incremental emissions concatenate to exactly the
    tokens the non-streamed path generates — transport, not sampling."""
    servers, _ = fleet
    eng = servers[0].engine
    prompt = [3, 4, 5, 6, 7]
    kw = dict(top_k=1, termination_id=10 ** 9, return_log_probs=True)
    ref = eng.submit(prompt, 10, **kw)
    eng.run_until_idle()
    ref_toks, ref_lp = ref.result(timeout=10)
    req, q = eng.submit_stream(prompt, 10, **kw)
    eng.run_until_idle()
    toks, lps = [], []
    for ev in q.iter_events(timeout=10.0):
        if ev.kind == "token":
            toks += ev.tokens
            lps += ev.log_probs
        else:
            assert ev.kind == "done"
            assert ev.data["timing"]["tokens"] == len(toks)
            assert ev.data["timing"]["ttft_s"] is not None
    assert toks == list(ref_toks)[len(prompt):]
    assert lps == pytest.approx(list(ref_lp))


def test_replica_sse_done_body_identical_to_buffered(fleet):
    """The acceptance bar, replica-direct: a "stream": true request's
    terminal done frame carries the byte-identical generation payload of
    the buffered request, headers (trace id + TTFT stamp) precede the
    body, and the incremental frames actually stream tokens."""
    _, urls = fleet
    payload = {"prompts": ["stream me please"], **GEN}
    code, buffered = _put(urls[0] + "/api", payload)
    assert code == 200
    tid = "stream-identity-test"
    code, hdrs, frames, _ = _stream_put(
        urls[0], {**payload, "stream": True},
        headers={"X-MLT-Trace-Id": tid})
    assert code == 200
    assert hdrs["X-MLT-Trace-Id"] == tid
    assert float(hdrs["X-MLT-TTFT-S"]) > 0.0
    kinds = [e for e, _ in frames]
    assert kinds[-1] == "done" and kinds.count("done") == 1
    token_frames = [d for e, d in frames if e == "token"]
    assert token_frames, "no incremental token frames streamed"
    assert all(d["tokens"] for d in token_frames)
    done = frames[-1][1]
    # timing is per-serve metadata (ISSUE 12); the generation is not
    assert done.pop("timing", None) is not None
    buffered.pop("timing", None)
    assert done == buffered, "streaming changed the tokens"


def test_replica_health_advertises_streaming_and_registered(fleet):
    _, urls = fleet
    with urllib.request.urlopen(urls[0] + "/health", timeout=10) as resp:
        info = json.loads(resp.read())
    assert info["streaming"] is True
    assert info["registered"] is False  # no --register_url on this fixture


def test_stream_validation_rejects_unstreamable_requests(fleet):
    _, urls = fleet
    bad = [
        ({"prompts": ["a", "b"], "tokens_to_generate": 4, "stream": True},
         "exactly one prompt"),
        ({"prompts": ["a"], "tokens_to_generate": 4, "beam_width": 2,
          "stream": True}, "beam"),
        ({"prompts": ["a"], "tokens_to_generate": 0, "stream": True},
         "tokens_to_generate"),
        ({"prompts": ["a"], "tokens_to_generate": 4, "stream": "yes"},
         "boolean"),
    ]
    for payload, needle in bad:
        code, body = _put(urls[0] + "/api", payload)
        assert code == 400 and needle in body["error"], (payload, body)


def test_router_stream_passthrough_identical_and_traced(fleet):
    """Streamed through the router == streamed direct == buffered, with
    one trace id spanning both tiers (echoed header + replica timing)."""
    from megatron_llm_tpu.serving.router.server import RouterServer

    _, urls = fleet
    router = RouterServer(urls, policy="round_robin", poll_interval=30.0)
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"
        payload = {"prompts": ["route the stream"], **GEN, "stream": True}
        tid = "router-stream-trace"
        code, hdrs, frames, _ = _stream_put(
            base, payload, headers={"X-MLT-Trace-Id": tid})
        assert code == 200
        assert hdrs["X-MLT-Trace-Id"] == tid
        assert float(hdrs["X-MLT-TTFT-S"]) > 0.0
        assert [e for e, _ in frames][-1] == "done"
        routed_done = frames[-1][1]
        # the replica resolved the SAME trace id (its flight record
        # produced the timing block under that id)
        assert routed_done.pop("timing", None) is not None
        for u in urls:
            code, _, direct, _ = _stream_put(u, payload)
            assert code == 200
            d = direct[-1][1]
            d.pop("timing", None)
            assert routed_done == d, "routing changed the streamed tokens"
        code, buffered = _put(base + "/api",
                              {k: v for k, v in payload.items()
                               if k != "stream"})
        assert code == 200
        buffered.pop("timing", None)
        assert routed_done == buffered
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# Mid-stream death + admission + discovery (programmable fake replicas)
# ---------------------------------------------------------------------------


class _FakeStreamReplica:
    """Minimal /api + /health replica; ``mode`` picks the PUT behavior:
    'ok' buffered JSON, 'sse' a well-terminated stream, 'die_mid_stream'
    two token frames then FIN with no terminal frame, 'slow_ok' buffered
    after ``delay`` (capacity 1 — concurrent requests get 503)."""

    def __init__(self, mode="ok", delay=0.2):
        self.mode = mode
        self.delay = delay
        self.requests = 0
        self.health_polls = 0
        self._busy = threading.Semaphore(1)
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_PUT(self):
                outer.requests += 1
                if outer.mode in ("sse", "die_mid_stream"):
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Connection", "close")
                    self.send_header("X-MLT-TTFT-S", "0.001")
                    self.end_headers()
                    self.wfile.write(sse_encode("token", {"tokens": [1]}))
                    self.wfile.write(sse_encode("token", {"tokens": [2]}))
                    self.wfile.flush()
                    if outer.mode == "sse":
                        self.wfile.write(sse_encode(
                            "done", {"text": ["ok"], "served_by": outer.url}))
                        self.wfile.flush()
                    else:
                        self.connection.shutdown(socket.SHUT_WR)
                    return
                if outer.mode == "slow_ok":
                    if not outer._busy.acquire(blocking=False):
                        body = json.dumps({"error": "queue full",
                                           "retry_after": 0.05}).encode()
                        self.send_response(503)
                        self.send_header("Retry-After", "1")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    try:
                        time.sleep(outer.delay)
                    finally:
                        outer._busy.release()
                body = json.dumps({"text": ["ok"],
                                   "served_by": outer.url}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                outer.health_polls += 1
                body = json.dumps({
                    "status": "ok", "replica_id": outer.url,
                    "seq": outer.health_polls, "uptime_s": 1.0,
                    "active_slots": 0, "max_slots": 1, "queued": 0,
                    "streaming": True, "registered": True,
                }).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_router_midstream_death_is_structured_never_silent():
    """Once the first body byte is forwarded the request is committed:
    a replica dying mid-stream yields a terminal SSE error frame (the
    client can tell completion from truncation), feeds the breaker, and
    is never retried — the healthy twin sees zero requests."""
    from megatron_llm_tpu.serving.router import SUSPECT
    from megatron_llm_tpu.serving.router.server import RouterServer

    dying = _FakeStreamReplica(mode="die_mid_stream")
    healthy = _FakeStreamReplica(mode="sse")
    router = RouterServer([dying.url, healthy.url], policy="round_robin",
                          poll_interval=30.0)
    try:
        port = router.start_background()
        payload = {"prompts": ["x"], "tokens_to_generate": 4,
                   "stream": True}
        for _ in range(2):  # round_robin: one request lands on each
            code, _, frames, _ = _stream_put(
                f"http://127.0.0.1:{port}", payload)
            assert code == 200
            kinds = [e for e, _ in frames]
            assert kinds[-1] in ("done", "error"), (
                f"silent truncation: stream ended with {kinds}")
            if kinds[-1] == "error":
                data = frames[-1][1]
                assert data["truncated"] is True
                assert data["replica"] == dying.url
                assert "not retried" in data["error"]
        assert dying.requests == 1 and healthy.requests == 1, (
            "a mid-stream death must never be retried")
        assert router.registry.get(dying.url).state == SUSPECT
        assert router.registry.get(healthy.url).state != SUSPECT
    finally:
        router.stop()
        dying.stop()
        healthy.stop()


def test_router_admission_queue_absorbs_burst():
    """A saturation burst against a capacity-1 replica: without the
    admission queue (and no proxy retries) some requests eat 503s; with
    it, arrivals wait their turn and 0 requests are dropped."""
    from megatron_llm_tpu.serving.router.server import RouterServer

    def burst(port, n=6):
        codes = [None] * n

        def worker(i):
            codes[i] = _put(f"http://127.0.0.1:{port}/api",
                            {"prompts": ["b"], "tokens_to_generate": 1})[0]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return codes

    rep = _FakeStreamReplica(mode="slow_ok", delay=0.15)
    baseline = RouterServer([rep.url], poll_interval=30.0, max_retries=0)
    try:
        port = baseline.start_background()
        codes = burst(port)
        assert 503 in codes, "burst too small to saturate the baseline"
    finally:
        baseline.stop()
        rep.stop()

    rep = _FakeStreamReplica(mode="slow_ok", delay=0.15)
    gated = RouterServer([rep.url], poll_interval=30.0, max_retries=0,
                         admission_depth=16, admission_limit=1,
                         admission_timeout_s=30.0)
    try:
        port = gated.start_background()
        codes = burst(port)
        assert codes == [200] * len(codes), (
            f"admission queue dropped requests: {codes}")
        # the handler releases AFTER writing the body — give the last
        # server thread a beat to reach its finally block
        deadline = time.monotonic() + 5
        while (gated.admission.stats()["inflight"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        stats = gated.admission.stats()
        assert stats["overflows"] == 0 and stats["inflight"] == 0
    finally:
        gated.stop()
        rep.stop()


def test_elastic_registration_lifecycle():
    """A router started with zero static replicas admits a registering
    replica (immediately routable), expires it through the breaker when
    it dies, and re-admits its restart on a new port."""
    from megatron_llm_tpu.serving.router import EJECTED, HEALTHY
    from megatron_llm_tpu.serving.router.server import RouterServer

    router = RouterServer([], allow_registration=True, poll_interval=30.0,
                          eject_after=2)
    rep = _FakeStreamReplica(mode="ok")
    try:
        port = router.start_background()
        base = f"http://127.0.0.1:{port}"
        code, body = _put(base + "/api",
                          {"prompts": ["x"], "tokens_to_generate": 1})
        assert code == 503, "an empty elastic fleet sheds, it can't route"

        def register(url):
            req = urllib.request.Request(
                base + "/admin/register",
                data=json.dumps({"replica": url}).encode(), method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.loads(resp.read())

        ack = register(rep.url)
        assert ack["added"] is True and ack["state"] == HEALTHY
        # registration polled synchronously: routable with no extra wait
        code, body = _put(base + "/api",
                          {"prompts": ["x"], "tokens_to_generate": 1})
        assert code == 200 and body["served_by"] == rep.url
        assert register(rep.url)["added"] is False  # heartbeat no-op
        # /health marks the replica as discovered, not statically configured
        with urllib.request.urlopen(base + "/health", timeout=10) as resp:
            rows = json.loads(resp.read())["replicas"]
        assert [r["registered"] for r in rows] == [True]

        # kill it; drive the breaker the way the poll loop would
        dead_url = rep.url
        rep.stop()
        replica = router.registry.get(dead_url)
        for _ in range(2):
            router.poller.poll_once(replica)
        assert replica.state == EJECTED

        # restart on a new port: a fresh registration re-enters the fleet
        rep = _FakeStreamReplica(mode="ok")
        assert register(rep.url)["added"] is True
        code, body = _put(base + "/api",
                          {"prompts": ["x"], "tokens_to_generate": 1})
        assert code == 200 and body["served_by"] == rep.url
    finally:
        router.stop()
        rep.stop()


def test_register_endpoint_403_when_registration_disabled():
    from megatron_llm_tpu.serving.router.server import RouterServer

    rep = _FakeStreamReplica(mode="ok")
    router = RouterServer([rep.url], poll_interval=30.0)
    try:
        port = router.start_background()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/admin/register",
            data=json.dumps({"replica": "http://127.0.0.1:1"}).encode(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 403
    finally:
        router.stop()
        rep.stop()


def test_run_router_allows_empty_fleet_only_with_registration(monkeypatch):
    """tools/run_router.py: --allow_registration lifts the static-replica
    requirement (argparse-level contract, no sockets)."""
    import tools.run_router as rr
    from megatron_llm_tpu.serving.router.server import RouterServer

    with pytest.raises(SystemExit):
        rr.main(["--policy", "least_loaded"])  # still required without it

    seen = {}

    def fake_bind(self, host, port):
        seen["registration"] = self.allow_registration
        seen["admission"] = self.admission
        return 0

    monkeypatch.setattr(RouterServer, "bind", fake_bind)
    monkeypatch.setattr(RouterServer, "serve", lambda self: None)
    rr.main(["--allow_registration", "--admission_queue_depth", "8",
             "--port", "0"])
    assert seen["registration"] is True
    assert seen["admission"] is not None and seen["admission"].depth == 8


# ---------------------------------------------------------------------------
# One stream writer (ISSUE 34, serving/streaming/writer.py): a tick's
# frames leave from ONE thread; a handler thread writes its stream's first
# frame and its terminal frames and is woken for nothing else
# ---------------------------------------------------------------------------


def _toy_engine(max_slots=8, tokenizer=None):
    import jax

    from megatron_llm_tpu.generation import ContinuousBatchingEngine
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
    return ContinuousBatchingEngine(cfg, params, tokenizer or ToyTokenizer(),
                                    max_slots=max_slots, max_seq=128)


@pytest.fixture(scope="module")
def writer_server():
    """One replica of 8 slots whose frames are fat (64 characters a
    token), so a reader that stops fills its socket within a request."""
    from megatron_llm_tpu.generation.server import MegatronServer
    from tests.test_generation import ToyTokenizer

    class FatTokenizer(ToyTokenizer):
        def detokenize(self, ids):
            return "".join(chr(97 + (i % 26)) * 64 for i in ids if i >= 2)

    srv = MegatronServer(_toy_engine(tokenizer=FatTokenizer()))
    port = srv.start_background(port=0)
    yield srv, f"http://127.0.0.1:{port}"
    srv.stop()


def _counter(name):
    from megatron_llm_tpu.observability.registry import get_registry

    return get_registry().counter(name).value


def _stream_raw(base, payload, timeout=120):
    """The response body of one streamed PUT, byte for byte."""
    u = urlparse(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    conn.request("PUT", "/api", body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    return resp.status, raw


def _streamed(frames):
    """(token ids, log-probs, text) of a stream's token frames, joined."""
    toks, lps, text = [], [], ""
    for event, data in frames:
        if event == "token":
            toks += data["tokens"]
            lps += data["logprobs"]
            text += data["text"]
    return toks, lps, text


def _open_stream(base, payload, rcvbuf=None):
    """Send a streamed PUT on a raw socket and read up to the first token
    frame; returns (socket, bytes read so far).  The caller decides
    whether anybody ever reads the rest."""
    u = urlparse(base)
    s = socket.socket()
    if rcvbuf is not None:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(60)
    s.connect((u.hostname, u.port))
    body = json.dumps(payload).encode()
    s.sendall(b"PUT /api HTTP/1.1\r\nHost: x\r\nContent-Type: "
              b"application/json\r\nContent-Length: %d\r\n\r\n%s"
              % (len(body), body))
    got = b""
    while b"event: token\n" not in got:
        chunk = s.recv(256)
        assert chunk, got
        got += chunk
    return s, got


def _wait(cond, seconds=60.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "condition not met in time"
        time.sleep(0.02)


@pytest.fixture(params=[None, 1e-5], ids=["switch-default", "switch-10us"])
def switch_interval(request):
    """The second case hands the interpreter over every 10 us: the
    writer, 8 handler threads and the scheduler interleave at every
    bytecode, where a token lost or reordered in the hand-over of a
    socket between a handler and the writer would show."""
    import sys

    old = sys.getswitchinterval()
    if request.param is not None:
        sys.setswitchinterval(request.param)
    try:
        yield
    finally:
        sys.setswitchinterval(old)


def test_writer_eight_streams_match_their_buffered_responses(
        writer_server, switch_interval):
    """(a) 8 concurrent streams through a live server: each stream's
    frames carry exactly the tokens and log-probs of the buffered
    response for the same seed, in order, and its done body is that
    response."""
    srv, url = writer_server
    eng = srv.engine
    prompts = [f"writer stream number {i}" for i in range(8)]
    gen = dict(tokens_to_generate=24, top_k=1, logprobs=True, random_seed=11)
    want = {}
    for p in prompts:
        code, body = _put(url + "/api", {"prompts": [p], **gen})
        assert code == 200
        body.pop("timing")
        _, _, lps, toks = eng.generate_and_post_process(
            [p], tokens_to_generate=24, return_output_log_probs=True,
            top_k_sampling=1, random_seed=11)
        want[p] = (body, toks[0][-24:], lps[0][-24:])
    got = {}

    def one(p):
        got[p] = _stream_put(url, {"prompts": [p], **gen, "stream": True})

    threads = [threading.Thread(target=one, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for p in prompts:
        code, _, frames, _ = got[p]
        body, toks, lps = want[p]
        assert code == 200
        assert [e for e, _ in frames if e != "token"] == ["done"]
        s_toks, s_lps, s_text = _streamed(frames)
        assert s_toks == list(toks)
        assert s_lps == pytest.approx(list(lps), abs=1e-6)
        done = frames[-1][1]
        assert done.pop("timing", None) is not None
        assert done == body, "streaming changed the response"
        assert done["logprobs"][0][-24:] == s_lps, "frames != own done body"
        assert done["text"][0].endswith(s_text)


def test_writer_stalled_reader_delays_nobody_and_drops_to_terminal(
        writer_server, monkeypatch):
    """(b) A client that stops reading stalls only itself: the streams
    opened after it run to their end while it sleeps.  Its own events
    wait in its bounded queue, and past the bound it gets ``dropped`` +
    a complete ``done`` whose count is the engine counter's."""
    srv, url = writer_server
    eng = srv.engine
    slow = "the reader that stops"
    real = eng.submit_stream_request

    def bounded(prompt, *a, **kw):
        if prompt == slow:
            kw["stream_events"] = 4
        return real(prompt, *a, **kw)

    monkeypatch.setattr(eng, "submit_stream_request", bounded)
    gen = dict(tokens_to_generate=100, top_k=1, logprobs=True, random_seed=5)
    _, _, _, ref = eng.generate_and_post_process(
        [slow], tokens_to_generate=100, top_k_sampling=1, random_seed=5)
    ref = list(ref[0][-100:])
    dropped0 = _counter("mlt_engine_stream_dropped_events_total")
    deferred0 = _counter("mlt_server_stream_deferred_sends_total")
    # accepted sockets inherit the listener's buffer size: the smallest
    # the kernel gives, for the stalled connection alone
    lsock = srv._httpd.socket
    sndbuf = lsock.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF)
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
    try:
        stalled, head = _open_stream(
            url, {"prompts": [slow], **gen, "stream": True}, rcvbuf=1)
    finally:
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
    try:
        others = {}

        def one(i):
            others[i] = _stream_put(url, {
                "prompts": [f"a reader that reads {i}"], **gen,
                "stream": True})

        t0 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        took = time.monotonic() - t0
        assert took < 30.0, f"streams behind a stalled reader took {took}s"
        for i in range(3):
            code, _, frames, _ = others[i]
            assert code == 200 and frames[-1][0] == "done"
            assert len(_streamed(frames)[0]) == 100
        # the stalled request's generation ends too, on the engine's time
        _wait(lambda: srv.health()["active_slots"] == 0)
        dropped = _counter("mlt_engine_stream_dropped_events_total") - dropped0
        assert dropped >= 1, "the socket never filled: nothing was tested"
        assert _counter("mlt_server_stream_deferred_sends_total") > deferred0
        # now it reads on: everything queued for it arrives, in order
        raw = head
        while True:
            chunk = stalled.recv(65536)
            if not chunk:
                break
            raw += chunk
    finally:
        stalled.close()
    frames = parse_sse(raw.split(b"\r\n\r\n", 1)[1])
    kinds = [e for e, _ in frames]
    assert kinds[-2:] == ["dropped", "done"] and kinds.count("done") == 1
    assert frames[-2][1]["dropped_events"] == dropped
    toks, lps, _ = _streamed(frames)
    assert len(toks) == len(lps) == 100 - dropped  # one token an event
    it = iter(ref)
    assert all(t in it for t in toks), "frames out of order"
    done = frames[-1][1]
    assert len(done["logprobs"][0]) == len(done["segments"][0]) - 1
    assert len(done["segments"][0]) >= 100


def test_writer_survives_a_client_that_disconnects(writer_server):
    """(c) A client that goes away mid-stream abandons its queue; the
    writer drops the connection and goes on serving."""
    srv, url = writer_server
    gen = dict(tokens_to_generate=100, top_k=1, random_seed=3)
    s, _ = _open_stream(url, {"prompts": ["gone before the end"], **gen,
                              "stream": True})
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 b"\x01\x00\x00\x00\x00\x00\x00\x00")  # close with a reset
    s.close()
    _wait(lambda: srv.health()["active_slots"] == 0)
    _wait(lambda: not srv._stream_writer._streams)
    assert srv._stream_writer._thread.is_alive()
    code, raw = _stream_raw(url, {"prompts": ["still served"], **gen,
                                  "stream": True})
    frames = parse_sse(raw)
    assert code == 200 and frames[-1][0] == "done"
    assert len(_streamed(frames)[0]) == 100


@pytest.mark.parametrize("n_tokens", [1, 2, 40])
def test_writer_terminal_frame_is_the_last_bytes(writer_server, n_tokens):
    """(d) Whoever wrote the frames before it, ``done`` is the end of the
    body: also for a request that ends on the tick of its first token."""
    _, url = writer_server
    code, raw = _stream_raw(url, {
        "prompts": ["how it ends"], "tokens_to_generate": n_tokens,
        "top_k": 1, "logprobs": True, "stream": True})
    assert code == 200
    frames = parse_sse(raw)
    assert [e for e, _ in frames if e != "token"] == ["done"]
    assert frames[-1][0] == "done"
    assert raw.endswith(sse_encode("done", frames[-1][1]))
    assert len(_streamed(frames)[0]) == n_tokens


def test_writer_engagement_counters(writer_server):
    """(e) A streamed request wakes its handler thread twice, however
    many tokens it streams, and one writer pass sends several streams'
    frames."""
    _, url = writer_server
    names = ("mlt_server_stream_requests_total",
             "mlt_server_stream_handler_wakeups_total",
             "mlt_server_stream_frames_total",
             "mlt_server_stream_writer_passes_total")
    before = {n: _counter(n) for n in names}
    out = {}

    def one(i):
        out[i] = _stream_put(url, {
            "prompts": [f"counted stream {i}"], "tokens_to_generate": 60,
            "top_k": 1, "stream": True})

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(out[i][2][-1][0] == "done" for i in range(6))
    d = {n: _counter(n) - before[n] for n in names}
    assert d["mlt_server_stream_requests_total"] == 6
    # the first token and the terminal event (one fewer where an event was
    # in before its handler came to wait); a wake-up a token would be 360
    assert 6 < d["mlt_server_stream_handler_wakeups_total"] <= 12
    assert d["mlt_server_stream_writer_passes_total"] >= 1
    # every token but a stream's first (and what its handler sent at the
    # end) went through the writer, several streams a pass
    assert d["mlt_server_stream_frames_total"] >= 6 * 40 / 2
    assert (d["mlt_server_stream_frames_total"]
            > 1.5 * d["mlt_server_stream_writer_passes_total"])


def test_server_stop_joins_the_stream_writer():
    """(f) The writer lives exactly as long as the server serves."""
    from megatron_llm_tpu.generation.server import MegatronServer

    def writers():
        return [t for t in threading.enumerate()
                if t.name == "stream-writer" and t.is_alive()]

    n0 = len(writers())
    eng = _toy_engine(max_slots=2)
    srv = MegatronServer(eng)
    assert len(writers()) == n0, "no thread before the server starts"
    port = srv.start_background(port=0)
    assert len(writers()) == n0 + 1 and eng.stream_kick is not None
    code, raw = _stream_raw(f"http://127.0.0.1:{port}", {
        "prompts": ["one stream"], "tokens_to_generate": 8, "top_k": 1,
        "stream": True})
    assert code == 200 and parse_sse(raw)[-1][0] == "done"
    srv.stop()
    assert len(writers()) == n0 and eng.stream_kick is None


def test_scheduler_never_sends_and_a_deaf_fleet_of_sockets_costs_it_nothing():
    """(g) Nothing on the scheduler's path touches a socket: with every
    attached connection refusing every byte, the thread that drives
    ``step()`` runs the requests to their end, every send was the
    writer thread's, and the events wait in (and are shed from) their
    own bounded queues."""
    from megatron_llm_tpu.serving.streaming import StreamWriter

    class DeafSocket:
        def __init__(self):
            self.senders = set()

        def send(self, data, flags=0):
            self.senders.add(threading.current_thread().name)
            assert flags & socket.MSG_DONTWAIT, "a send that could block"
            raise BlockingIOError

    eng = _toy_engine(max_slots=4)
    writer = StreamWriter()
    writer.start()
    eng.stream_kick = writer.kick
    deferred0 = _counter("mlt_server_stream_deferred_sends_total")
    try:
        socks, queues, attached = [], [], []
        for i in range(4):
            _, q = eng.submit_stream([3 + i, 4, 5, 6], 40, top_k=1,
                                     termination_id=10 ** 9,
                                     stream_events=8)
            socks.append(DeafSocket())
            queues.append(q)
            attached.append(writer.attach(q, socks[-1]))
        driver = threading.Thread(target=eng.run_until_idle,
                                  name="the-scheduler")
        driver.start()
        driver.join(timeout=120)
        assert not driver.is_alive(), "step() waited on a socket"
        _wait(lambda: _counter("mlt_server_stream_deferred_sends_total")
              > deferred0)
    finally:
        writer.stop()
    for sock, q, stream in zip(socks, queues, attached):
        assert sock.senders == {"stream-writer"}
        assert q.wait_terminal(gap_timeout=1.0)
        evs = list(q.iter_events(timeout=0.0))
        assert evs[-1].kind == "done"
        # one frame is in the writer's hands (built, never taken by the
        # socket); what came after it waited in the queue up to its bound
        # and was shed past it: no token is lost uncounted
        (_, frame), = parse_sse(writer.detach(stream))
        held = sum(len(e.tokens) for e in evs[:-1])
        assert len(evs) - 1 <= 8
        assert evs[-1].data["dropped_events"] == q.dropped >= 1
        assert len(frame["tokens"]) + held + q.dropped == 40


def test_stream_queue_drain_and_park_share_the_ordering():
    """``take_tokens`` (the writer's drain) and ``next_event`` read one
    order; a parked ``wait_terminal`` is not woken by tokens."""
    q = StreamQueue(maxsize=8)
    q.publish_tokens([1], [0.1])
    q.publish_tokens([2, 3], [0.2, 0.3])
    assert [e.tokens for e in q.take_tokens()] == [[1], [2, 3]]
    assert q.take_tokens() == []
    ended = []
    parked = threading.Thread(
        target=lambda: ended.append(q.wait_terminal(gap_timeout=30.0)))
    parked.start()
    for t in range(5):
        q.publish_tokens([10 + t], [0.0])
    time.sleep(0.1)
    assert q.wakeups == 0 and not ended, "a token woke the parked handler"
    q.publish_terminal(StreamEvent("done", data={}))
    parked.join(timeout=10)
    assert ended == [True] and q.wakeups == 1
    assert [e.tokens for e in q.take_tokens()] == [[10 + t] for t in range(5)]
    assert q.take_tokens() == [], "the terminal is never the writer's"
    assert q.next_event(timeout=0.0).kind == "done"
    # a generation that publishes nothing for a whole gap has stalled
    assert StreamQueue().wait_terminal(gap_timeout=0.05) is False
