"""REST server contract tests (reference analog: none — the reference server
is untested; we gate on the documented wire contract of
text_generation_server.py: PUT /api validation messages and response keys)."""

import json
import urllib.request

import jax
import pytest

from megatron_llm_tpu.generation import InferenceEngine
from megatron_llm_tpu.generation.server import MegatronServer, _validate
from megatron_llm_tpu.models import init_model_params, make_config

from tests.test_generation import VOCAB, ToyTokenizer


@pytest.fixture(scope="module")
def server():
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, ToyTokenizer())
    srv = MegatronServer(engine)
    port = srv.start_background(port=0)  # ephemeral port
    yield f"http://127.0.0.1:{port}"
    srv.stop()


def _put(url, payload):
    req = urllib.request.Request(
        url + "/api", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="PUT",
    )
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_validation_messages():
    assert _validate({})[1] == "prompts argument required"
    assert _validate({"prompts": "x"})[1] == "prompts is not a list of strings"
    assert _validate({"prompts": []})[1] == "prompts is empty"
    assert _validate({"prompts": ["a"], "max_len": 3})[1].startswith(
        "max_len is no longer used")
    assert _validate({"prompts": ["a"], "tokens_to_generate": 0})[1] == \
        "tokens_to_generate=0 implies logprobs should be True"
    assert _validate({"prompts": ["a"], "top_k": 3, "top_p": 0.5})[1] == \
        "cannot set both top-k and top-p samplings."
    assert _validate({"prompts": ["a", "b"], "beam_width": 2})[1] == \
        "When doing beam_search, batch size must be 1"
    params, err = _validate({"prompts": ["a"], "tokens_to_generate": 8})
    assert err is None and params["tokens_to_generate"] == 8
    assert params["use_eod_token_for_early_termination"] is True
    params, err = _validate({"prompts": ["a"],
                             "use_eod_token_for_early_termination": False})
    assert err is None and not params["use_eod_token_for_early_termination"]
    assert _validate({"prompts": ["a"],
                      "use_eod_token_for_early_termination": 0})[1] == \
        "use_eod_token_for_early_termination must be a boolean value"


def test_server_generate_roundtrip(server):
    status, body = _put(server, {
        "prompts": ["hello"], "tokens_to_generate": 4, "top_k": 1,
        "logprobs": True,
    })
    assert status == 200
    assert set(body) == {"text", "segments", "logprobs"}
    assert len(body["text"]) == 1 and isinstance(body["text"][0], str)
    assert len(body["logprobs"][0]) == len(body["segments"][0]) - 1


def test_server_beam_roundtrip(server):
    status, body = _put(server, {
        "prompts": ["hello"], "tokens_to_generate": 4, "beam_width": 2,
        "stop_token": VOCAB + 9,
    })
    assert status == 200
    assert set(body) == {"text", "segments", "scores"}
    assert len(body["text"]) == 2


def test_server_rejects_bad_request(server):
    status, body = _put(server, {"prompts": []})
    assert status == 400


def test_server_rejects_overlong_request(server):
    """prompt + tokens_to_generate > max_position_embeddings -> 400 with the
    reference's message (generation.py:133-135)."""
    status, body = _put(server, {
        "prompts": ["hello"], "tokens_to_generate": 100000})
    assert status == 400
    assert "longer than allowed" in body


def test_server_serves_ui(server):
    with urllib.request.urlopen(server + "/") as resp:
        assert resp.status == 200
        assert b"Generate" in resp.read()


def test_server_structured_json_errors(server):
    """Errors are {"error": msg} JSON with proper status codes — including
    payloads that are valid JSON but not objects (previously a 500 with a
    bare traceback path)."""
    status, body = _put(server, ["not", "an", "object"])
    assert status == 400
    assert json.loads(body)["error"] == "request body must be a JSON object"

    status, body = _put(server, {"prompts": []})
    assert status == 400
    assert "prompts is empty" in json.loads(body)["error"]

    status, body = _put(server, {"prompts": ["x"], "tokens_to_generate": 10 ** 6})
    assert status == 400
    assert "longer than allowed" in json.loads(body)["error"]

    req = urllib.request.Request(
        server + "/api", data=b"{not json", method="PUT")
    try:
        urllib.request.urlopen(req)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert json.loads(e.read().decode())["error"] == "invalid JSON"


# ---------------------------------------------------------------------------
# Continuous-batching server (generation/engine.py behind the same wire)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def batching_server():
    from megatron_llm_tpu.generation import ContinuousBatchingEngine

    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    engine = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                      max_slots=8, max_seq=128)
    srv = MegatronServer(engine)
    port = srv.start_background(port=0)
    yield f"http://127.0.0.1:{port}", engine
    srv.stop()


def test_batching_server_same_wire_contract(batching_server):
    url, _ = batching_server
    status, body = _put(url, {
        "prompts": ["hello"], "tokens_to_generate": 4, "top_k": 1,
        "logprobs": True,
    })
    assert status == 200
    # ISSUE 12 extends the wire contract with server-side timing
    # metadata (trace id, first-token time, latency decomposition)
    assert set(body) == {"text", "segments", "logprobs", "timing"}
    assert body["timing"]["ttft_s"] is not None
    assert len(body["logprobs"][0]) == len(body["segments"][0]) - 1


def test_batching_server_concurrent_requests_share_ticks(batching_server):
    """Concurrent HTTP requests are admitted into shared decode ticks: all
    succeed, and the engine ticked far fewer times than the serialized
    one-tick-per-token count."""
    import threading

    url, engine = batching_server
    ticks0, n, gen_len = engine.ticks, 6, 12
    results = [None] * n

    def worker(i):
        results[i] = _put(url, {
            "prompts": [f"prompt number {i}"], "tokens_to_generate": gen_len,
            "top_k": 1,
        })

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(status == 200 for status, _ in results)
    assert all(len(body["segments"][0]) > 0 for _, body in results)
    # serialized decode would need ~n * gen_len ticks; sharing needs far
    # fewer (admission order may stagger slightly under thread scheduling)
    assert engine.ticks - ticks0 < n * gen_len


def test_batching_server_health_endpoint(batching_server):
    url, _ = batching_server
    with urllib.request.urlopen(url + "/health") as resp:
        assert resp.status == 200
        info = json.loads(resp.read())
    assert info["status"] == "ok" and info["batching"] is True
    assert info["free_pages"] == info["total_pages"]  # idle between tests


def test_server_cli_knows_no_depth_flag_and_health_names_no_depth(
        batching_server, monkeypatch):
    """The pipelined step went with its option (PR 47): the depth flag is
    an argparse error to the program's parser, like any flag it does not
    know; the server's command line, which hands the flags it does not
    know on (``parse_known_args``), passes over it as over any of them
    (and goes on to refuse a flag it does know); no configuration holds
    such a field, and ``/health`` names none.  (The name is spelled in
    two halves: a search of the tree for it finds nothing.)"""
    import importlib.util
    from pathlib import Path

    from megatron_llm_tpu.config.arguments import build_parser, parse_args

    field = "tick_" + "pipeline_depth"
    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(["--" + field, "2"])
    assert e.value.code == 2
    build_parser().parse_args(["--spec_k", "2"])      # a flag it knows

    spec = importlib.util.spec_from_file_location(
        "run_text_generation_server",
        Path(__file__).parent.parent / "tools"
        / "run_text_generation_server.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    argv = ["--tokenizer_type", "NullTokenizer", "--vocab_size", "128",
            "--" + field, "2"]
    monkeypatch.setattr("sys.argv", [
        "run_text_generation_server.py", "--random_init", *argv,
        "--prefill_chunk", "24"])
    with pytest.raises(ValueError, match="positive whole number of pages"):
        tool.main()
    cfg = parse_args(["--model_name", "llama2", *argv])
    assert not hasattr(cfg.inference, field)
    url, _ = batching_server
    with urllib.request.urlopen(url + "/health") as resp:
        assert field not in json.loads(resp.read())


def test_batching_server_health_reports_cache_and_queue(batching_server):
    """ISSUE 5: /health carries prefix-cache occupancy and queue depth."""
    url, engine = batching_server
    with urllib.request.urlopen(url + "/health") as resp:
        info = json.loads(resp.read())
    for field in ("pages_cached", "available_pages", "prefix_hit_tokens",
                  "prefix_miss_tokens", "queued", "prefilling"):
        assert field in info, f"missing {field}"
    assert info["pages_cached"] == len(engine.pool.cached)
    assert info["available_pages"] >= info["free_pages"]


def test_server_queue_overflow_returns_503_with_retry_after():
    """ISSUE 5: backpressure is a structured JSON 503 with a Retry-After
    header, not an unbounded queue."""
    from megatron_llm_tpu.generation.engine import EngineOverloaded
    from megatron_llm_tpu.generation.server import MegatronServer

    class StuffedEngine:
        """Duck-typed batching engine whose queue is at capacity."""

        def submit(self, *a, **kw):
            raise EngineOverloaded("request queue full (2 waiting)",
                                   retry_after=3.0)

        def generate_and_post_process(self, *a, **kw):
            return self.submit()

        def start(self):
            pass

        def stop(self):
            pass

    srv = MegatronServer(StuffedEngine())
    code, body = srv.handle_request(
        {"prompts": ["hi"], "tokens_to_generate": 4})
    assert code == 503
    assert "queue full" in body["error"] and body["retry_after"] == 3.0

    port = srv.start_background(port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api",
            data=json.dumps({"prompts": ["hi"],
                             "tokens_to_generate": 4}).encode(),
            method="PUT")
        try:
            urllib.request.urlopen(req)
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers["Retry-After"] == "3"
            payload = json.loads(e.read().decode())
            assert "queue full" in payload["error"]
    finally:
        srv.stop()


def test_engine_failure_is_a_500_and_counted(batching_server):
    """A program that fails to lower, compile or run is the server's
    fault: a ValueError out of the engine's step (what a Pallas lowering
    refusal raises) answers 500 — never the 400 reserved for requests
    that can never be served — promptly, is counted in /health, and the
    scheduler thread survives to serve the next request."""
    url, engine = batching_server

    def refused(pre_rows):
        raise ValueError("The Pallas TPU lowering currently requires ...")

    real = engine._ragged_tick
    engine._ragged_tick = refused
    try:
        status, body = _put(url, {"prompts": ["hello"],
                                  "tokens_to_generate": 4, "top_k": 1})
    finally:
        engine._ragged_tick = real
    assert status == 500 and "Pallas TPU lowering" in body
    with urllib.request.urlopen(url + "/health") as resp:
        assert json.loads(resp.read())["engine_failures"] == 1
    status, _ = _put(url, {"prompts": ["hello"], "tokens_to_generate": 4,
                           "top_k": 1})
    assert status == 200
    status, body = _put(url, {"prompts": ["x"], "tokens_to_generate": 10 ** 6})
    assert status == 400 and "longer than allowed" in body
