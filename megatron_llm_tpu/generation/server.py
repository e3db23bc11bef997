"""REST text-generation server — megatron/text_generation_server.py analog.

Same wire contract (PUT /api, identical request fields/validation messages,
``{"text", "segments", "logprobs"}`` / ``{"text", "segments", "scores"}``
responses, GET / serves the static UI).  Differences by design:

* stdlib ``http.server`` (ThreadingHTTPServer) instead of Flask (not baked
  into the TPU image).
* No ``send_do_generate``/``send_do_beam_search`` rank broadcasts
  (text_generation_server.py:21-27): SPMD has one controller process, so
  the server just calls the engine.
* Errors are structured JSON (``{"error": msg}``) with proper status codes
  — a malformed payload can never surface as a bare-traceback 500.
* With the legacy dense engine the request lock serializes generations
  (programs are single-stream on the chip).  With the continuous-batching
  engine (generation/engine.py) the lock is NOT taken on the generate
  path: each handler thread enqueues its request and blocks on its future,
  so concurrent HTTP requests share decode ticks — the whole point of the
  engine.  Beam search stays behind the lock on either engine.
"""

from __future__ import annotations

import json
import socket
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Optional
from urllib.parse import parse_qs

from megatron_llm_tpu.generation.engine import EngineOverloaded
from megatron_llm_tpu.generation.generation import InvalidRequest
from megatron_llm_tpu.generation.scheduling import RequestShed
from megatron_llm_tpu.observability import trace as obs_trace
from megatron_llm_tpu.observability.compiles import (
    install_compile_counter,
    startup_phase,
)
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.serving.streaming import (
    SSE_CONTENT_TYPE,
    StreamWriter,
    sse_encode,
    token_frame,
)

_STATIC_DIR = Path(__file__).parent / "static"
# the longest window GET /profile?ticks=N may ask for: the endpoint is on
# the public port and a capture's host buffers grow with every tick
MAX_PROFILE_TICKS = 1000


def _validate(payload: dict):
    """Field validation with the reference's messages
    (text_generation_server.py:31-178). Returns (params dict, error str)."""
    if "prompts" not in payload:
        return None, "prompts argument required"
    if "max_len" in payload:
        return None, "max_len is no longer used.  Replace with tokens_to_generate"
    if "sentences" in payload:
        return None, "sentences is no longer used.  Replace with prompts"
    prompts = payload["prompts"]
    if not isinstance(prompts, list):
        return None, "prompts is not a list of strings"
    if len(prompts) == 0:
        return None, "prompts is empty"
    if len(prompts) > 128:
        return None, "Maximum number of prompts is 128"

    p = {"prompts": prompts}

    tokens_to_generate = payload.get("tokens_to_generate", 64)
    if not isinstance(tokens_to_generate, int) or tokens_to_generate < 0:
        return None, "tokens_to_generate must be an integer greater than or equal to 0"
    p["tokens_to_generate"] = tokens_to_generate

    logprobs = payload.get("logprobs", False)
    if not isinstance(logprobs, bool):
        return None, "logprobs must be a boolean value"
    if tokens_to_generate == 0 and not logprobs:
        return None, "tokens_to_generate=0 implies logprobs should be True"
    p["logprobs"] = logprobs

    temperature = payload.get("temperature", 1.0)
    if not isinstance(temperature, (int, float)) or not 0.0 < temperature <= 100.0:
        return None, "temperature must be a positive number less than or equal to 100.0"
    p["temperature"] = float(temperature)

    top_k = payload.get("top_k", 0)
    if not isinstance(top_k, int) or not 0 <= top_k <= 1000:
        return None, ("top_k must be equal to or greater than 0 and less "
                      "than or equal to 1000")
    p["top_k"] = top_k

    top_p = payload.get("top_p", 0.0)
    if isinstance(top_p, int):
        top_p = float(top_p)
    if not isinstance(top_p, float) or not 0 <= top_p <= 1.0:
        return None, "top_p must be less than or equal to 1.0"
    if top_p > 0.0 and top_k > 0:
        return None, "cannot set both top-k and top-p samplings."
    p["top_p"] = top_p

    add_BOS = payload.get("add_BOS", False)
    if not isinstance(add_BOS, bool):
        return None, "add_BOS must be a boolean value"
    if any(len(prompt) == 0 for prompt in prompts) and not add_BOS:
        return None, "Empty prompts require add_BOS=true"
    p["add_BOS"] = add_BOS

    for flag in ("stop_on_double_eol", "stop_on_eol", "no_log"):
        val = payload.get(flag, False)
        if not isinstance(val, bool):
            return None, f"{flag} must be a boolean value"
        p[flag] = val

    # false: generate exactly tokens_to_generate tokens, whatever is sampled
    # (a rollout job's fixed lengths; an end-of-document id among random
    # weights' greedy tokens is noise)
    use_eod = payload.get("use_eod_token_for_early_termination", True)
    if not isinstance(use_eod, bool):
        return None, ("use_eod_token_for_early_termination must be a "
                      "boolean value")
    p["use_eod_token_for_early_termination"] = use_eod

    random_seed = payload.get("random_seed", -1)
    if not isinstance(random_seed, int):
        return None, "random_seed must be integer"
    if random_seed < -1:
        return None, "random_seed must be a positive integer"
    p["random_seed"] = random_seed

    # a block model's unmasking (generation/blocks.py); the engine checks
    # the values against its block length and refuses them for any other
    blocks = {}
    for name, kind in (("denoising_steps", int), ("remasking_strategy", str),
                       ("confidence_threshold", (int, float))):
        if name in payload:
            if not isinstance(payload[name], kind) or isinstance(
                    payload[name], bool):
                return None, f"{name} must be a {getattr(kind, '__name__', 'number')}"
            blocks[name] = payload[name]
    p["blocks"] = blocks

    beam_width = payload.get("beam_width")
    if beam_width is not None:
        if not isinstance(beam_width, int) or beam_width < 1:
            return None, "beam_width must be an integer > 1"
        if len(prompts) > 1:
            return None, "When doing beam_search, batch size must be 1"
    p["beam_width"] = beam_width

    stop_token = payload.get("stop_token", 50256)
    if not isinstance(stop_token, int):
        return None, "stop_token must be an integer"
    p["stop_token"] = stop_token

    length_penalty = payload.get("length_penalty", 1.0)
    if isinstance(length_penalty, int):
        length_penalty = float(length_penalty)
    if not isinstance(length_penalty, float):
        return None, "length_penalty must be a float"
    p["length_penalty"] = length_penalty

    # scheduling control plane (generation/scheduling/): priority class
    # for --sched_policy priority, soft deadlines for --sched_policy slo
    priority = payload.get("priority", 1)
    if not isinstance(priority, int) or not 0 <= priority <= 9:
        return None, "priority must be an integer between 0 and 9"
    p["priority"] = priority
    for field in ("ttft_deadline_ms", "tpot_deadline_ms"):
        val = payload.get(field)
        if val is not None and (not isinstance(val, (int, float))
                                or isinstance(val, bool) or val <= 0):
            return None, f"{field} must be a positive number of milliseconds"
        p[field] = None if val is None else float(val)

    # token streaming (ISSUE 18, serving/streaming/): SSE response
    # instead of a buffered body; transport-only, so the sampled tokens
    # are identical either way
    stream = payload.get("stream", False)
    if not isinstance(stream, bool):
        return None, "stream must be a boolean value"
    p["stream"] = stream
    return p, None


def _validate_stream(params: dict):
    """The extra constraints a ``"stream": true`` request must meet —
    streaming multiplexes ONE generation onto the response socket."""
    if len(params["prompts"]) != 1:
        return "streaming requires exactly one prompt"
    if params["beam_width"] is not None:
        return "beam search cannot stream"
    if params["tokens_to_generate"] == 0:
        return "streaming requires tokens_to_generate >= 1"
    return None


class _NullLock:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class MegatronServer:
    """text_generation_server.MegatronServer analog (:234-241)."""

    def __init__(self, engine, *, register_url: Optional[str] = None,
                 register_interval_s: float = 2.0,
                 advertise_url: Optional[str] = None,
                 role: str = "unified"):
        # the lock-relevant type (the legacy InferenceEngine has no
        # locks): the annotation below lets graftcheck's lock-order
        # graph resolve `with eng._lock:` in health()/metrics_text()
        self.engine = engine  # instance of ContinuousBatchingEngine
        self.lock = threading.Lock()
        # continuous-batching engines serialize device access internally
        # (enqueue + future); a server-level lock would undo the batching
        self.batching = hasattr(engine, "submit")
        self._httpd: Optional[ThreadingHTTPServer] = None
        # replica identity for the cross-replica router (serving/router/):
        # replica_id survives for the process lifetime, so a router sees a
        # restart as an id change; seq orders /health payloads so a stale
        # poll can never overwrite a fresher view; uptime_s is the
        # restart-detection cross-check (it must only move forward for the
        # same replica_id).  Schema: docs/guide/serving.md "/health payload".
        self.replica_id = uuid.uuid4().hex
        self._t_start = time.monotonic()
        self._health_seq = 0  # guarded by _seq_lock
        self._seq_lock = threading.Lock()
        # elastic discovery (ISSUE 18): with --register_url the replica
        # POSTs /admin/register heartbeats to the router, so the fleet
        # learns about it (and a restart on a new port) with no static
        # config; the router's breaker expires it when it goes silent
        self.register_url = register_url
        self.register_interval_s = register_interval_s
        self.advertise_url = advertise_url
        self._register_stop = threading.Event()
        self._register_thread: Optional[threading.Thread] = None
        # disaggregated prefill/decode (ISSUE 19, serving/handoff/): the
        # advertised serving role.  Roles steer the router's ``disagg``
        # policy; /api stays fully functional on every role (a role-less
        # or mixed fleet degrades to unified serving), but a prefill-role
        # replica refuses /admin/kv_push — it is a KV sender, not a sink.
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified', 'prefill' or 'decode', got {role!r}")
        self.role = role
        if role != "unified" and hasattr(engine, "refuse_handoff"):
            # a prefill or decode role exists to push and take KV pages
            engine.refuse_handoff()
        # token streaming: ONE thread writes every open stream's
        # incremental frames, kicked by the engine once an applied tick
        # (serving/streaming/writer.py); started and joined with the
        # server.  A handler thread writes its stream's first frame and
        # its terminal frames, and is woken for nothing else
        tok = getattr(engine, "tokenizer", None)
        self._detokenize = None if tok is None else tok.detokenize
        self._stream_writer = StreamWriter(self._detokenize)
        reg = obs_registry.get_registry()
        self._m_stream_requests = reg.counter(
            "mlt_server_stream_requests_total",
            help="streamed requests whose response body was begun")
        self._m_stream_wakeups = reg.counter(
            "mlt_server_stream_handler_wakeups_total",
            help="times a streamed request's handler thread came back "
                 "from a blocking wait on its queue (over "
                 "mlt_server_stream_requests_total: 2 a request, the "
                 "first token and the terminal event, however many "
                 "tokens it streamed)")

    def handle_request(self, payload, trace_id: str = ""):
        """Core PUT /api logic; returns (status_code, response dict).

        ``trace_id`` is the request's ``X-MLT-Trace-Id`` (minted by the
        HTTP handler when the caller/router sent none); it threads into
        the engine's flight record and spans, and 200 responses from
        batching engines carry a ``timing`` block derived from the
        flight record — the server-side first-token and latency
        decomposition the router's honest TTFT metric reads."""
        if not isinstance(payload, dict):
            return 400, {"error": "request body must be a JSON object"}
        if payload.get("handoff_to") is not None:
            # disaggregated prefill (ISSUE 19): prefill + export + push
            # instead of decoding; returns a migration receipt
            return self._prefill_handoff(payload, trace_id=trace_id)
        params, err = _validate(payload)
        if err:
            return 400, {"error": err}
        beam = params["beam_width"] is not None
        lock = self.lock if (beam or not self.batching) else _NullLock()
        with lock:
            try:
                if beam:
                    texts, segments, scores = self.engine.beam_search_and_post_process(
                        params["prompts"],
                        tokens_to_generate=params["tokens_to_generate"],
                        beam_size=params["beam_width"],
                        add_BOS=params["add_BOS"],
                        stop_token=params["stop_token"],
                        num_return_gen=params["beam_width"],
                        length_penalty=params["length_penalty"],
                    )
                    return 200, {"text": texts, "segments": segments,
                                 "scores": scores}
                kw = {}
                if self.batching:
                    # scheduling fields only exist on the batching engine
                    kw = dict(priority=params["priority"],
                              ttft_deadline_ms=params["ttft_deadline_ms"],
                              tpot_deadline_ms=params["tpot_deadline_ms"],
                              trace_id=trace_id)
                texts, segments, logprobs, _ = self.engine.generate_and_post_process(
                    params["prompts"],
                    tokens_to_generate=params["tokens_to_generate"],
                    return_output_log_probs=params["logprobs"],
                    top_k_sampling=params["top_k"],
                    top_p_sampling=params["top_p"],
                    temperature=params["temperature"],
                    add_BOS=params["add_BOS"],
                    stop_on_double_eol=params["stop_on_double_eol"],
                    stop_on_eol=params["stop_on_eol"],
                    use_eod_token_for_early_termination=params[
                        "use_eod_token_for_early_termination"],
                    random_seed=params["random_seed"],
                    **kw, **(params["blocks"] if self.batching else {}),
                )
                body = {"text": texts, "segments": segments,
                        "logprobs": logprobs}
                if self.batching and trace_id:
                    timing = self.request_timing(trace_id)
                    if timing is not None:
                        body["timing"] = timing
                return 200, body
            except EngineOverloaded as eo:
                # backpressure instead of unbounded queueing: structured
                # 503 + machine-readable retry hint (the HTTP handler turns
                # retry_after into a Retry-After header).  retry_after is
                # the engine's EMA drain estimate for the current queue
                # depth, and info carries the queue snapshot behind it.
                return 503, {"error": str(eo),
                             "retry_after": getattr(eo, "retry_after", 1.0),
                             **getattr(eo, "info", {})}
            except RequestShed as rs:
                # the scheduler refused the request (unmeetable deadline /
                # load shed) — retryable load feedback, not a client error
                return 503, {"error": str(rs), "shed": True,
                             "retry_after": getattr(rs, "retry_after", 1.0)}
            except InvalidRequest as ve:
                return 400, {"error": str(ve.args[0] if ve.args else ve)}
            except Exception as e:  # engine failure must still answer the client
                import traceback

                traceback.print_exc()
                return 500, {"error": f"internal error: {type(e).__name__}: {e}"}

    def _prefill_handoff(self, payload: dict, trace_id: str = ""):
        """Serve a ``"handoff_to": url`` request (ISSUE 19): run chunked
        prefill locally, export the prompt's full KV pages and push them
        to the decode replica at ``url``; the 200 answer is a migration
        receipt, not a generation.  The router sends these for long
        prompts (``disagg`` policy) and then forwards the original
        request to the decode replica, which finds the pushed pages in
        its prefix cache.  A failed push is a 502 so the router can fall
        back to unified serving — the request is never half-served."""
        from megatron_llm_tpu.serving.handoff.transfer import (
            KVPushError, push_pages)

        target = payload.get("handoff_to")
        if not isinstance(target, str) or not target.strip():
            return 400, {"error": "handoff_to must be a replica base URL"}
        if not self.batching or not hasattr(self.engine, "prefill_and_export"):
            return 400, {"error":
                         "handoff requires the continuous-batching engine"}
        params, err = _validate(
            {k: v for k, v in payload.items() if k != "handoff_to"})
        if err:
            return 400, {"error": err}
        if len(params["prompts"]) != 1:
            return 400, {"error": "handoff requires exactly one prompt"}
        if params["beam_width"] is not None:
            return 400, {"error": "beam search cannot hand off"}
        if params["logprobs"]:
            # logprobs requests bypass the prefix trie on the decode
            # side, so pushed pages could never be used — refuse rather
            # than do the work for nothing
            return 400, {"error": "handoff cannot serve logprobs requests"}
        try:
            blob, info = self.engine.prefill_and_export(
                params["prompts"][0], add_BOS=params["add_BOS"],
                trace_id=trace_id)
        except EngineOverloaded as eo:
            return 503, {"error": str(eo),
                         "retry_after": getattr(eo, "retry_after", 1.0),
                         **getattr(eo, "info", {})}
        except RequestShed as rs:
            return 503, {"error": str(rs), "shed": True,
                         "retry_after": getattr(rs, "retry_after", 1.0)}
        except InvalidRequest as ve:
            return 400, {"error": str(ve.args[0] if ve.args else ve)}
        except Exception as e:
            import traceback

            traceback.print_exc()
            return 500, {"error": f"internal error: {type(e).__name__}: {e}"}
        receipt = {"target": target, "pages": info["pages"],
                   "bytes": info["bytes"], "tokens": info["tokens"],
                   "hit_tokens": info["hit_tokens"],
                   "replica_id": self.replica_id, "pushed": False}
        if info["pages"] == 0:
            # prompt shorter than one full page: nothing worth shipping
            return 200, {"handoff": receipt}
        try:
            receipt["receipt"] = push_pages(target, blob, trace_id=trace_id)
        except KVPushError as ke:
            body = {"error": str(ke), "handoff_failed": True}
            if ke.retry_after is not None:
                body["retry_after"] = ke.retry_after
            return 502, body
        receipt["pushed"] = True
        return 200, {"handoff": receipt}

    def kv_push(self, blob: bytes, trace_id: str = ""):
        """Core ``POST /admin/kv_push`` logic: install a handoff blob
        into this replica's pool/prefix cache (engine.import_kv) and
        answer with the import receipt.  Pool pressure is a structured
        503 + retry hint (the sender degrades to unified serving), a
        malformed or incompatible blob is a 400."""
        if self.role == "prefill":
            return 400, {"error":
                         "prefill-role replica does not accept KV pushes"}
        if not self.batching or not hasattr(self.engine, "import_kv"):
            return 400, {"error":
                         "kv_push requires the continuous-batching engine"}
        if not blob:
            return 400, {"error": "empty kv_push body"}
        try:
            receipt = self.engine.import_kv(blob, trace_id=trace_id)
        except EngineOverloaded as eo:
            return 503, {"error": str(eo),
                         "retry_after": getattr(eo, "retry_after", 1.0),
                         **getattr(eo, "info", {})}
        except ValueError as ve:
            return 400, {"error": str(ve.args[0] if ve.args else ve)}
        except Exception as e:
            import traceback

            traceback.print_exc()
            return 500, {"error": f"internal error: {type(e).__name__}: {e}"}
        receipt["replica_id"] = self.replica_id
        return 200, receipt

    def stream_response(self, handler, payload: dict, trace_id: str = ""):
        """Serve one ``"stream": true`` request as SSE on ``handler``'s
        socket (serving/streaming/, docs/guide/serving.md "Streaming").

        Returns None when the stream was served (headers + body written
        here), or ``(status, body)`` for a pre-stream failure — nothing
        has touched the socket yet, so the caller answers with the
        ordinary buffered path (same status codes, Retry-After, headers
        as a non-streamed request).

        The response headers (trace id + ``X-MLT-TTFT-S``) are sent at
        the moment the FIRST token event arrives — the stamp and the
        first flushed byte describe the same instant, which is the
        property the streaming bench gates on.

        This thread writes the first frame and the terminal frames
        (``dropped`` / ``done`` / ``error``).  Every frame between is the
        stream writer's: the socket is attached to it after the first
        frame and this thread parks until the stream has ended, then
        takes the socket back, sends what the writer had not (the rest
        of a frame a full socket buffer cut, events still queued) and the
        terminal.  It wakes twice a request, not once a token."""
        params, err = _validate(payload)
        if err is None:
            err = _validate_stream(params)
        if err:
            return 400, {"error": err}
        eng = self.engine
        if not self.batching or not hasattr(eng, "submit_stream_request"):
            return 400, {"error":
                         "streaming requires the continuous-batching engine"}
        try:
            req, q = eng.submit_stream_request(
                params["prompts"][0], params["tokens_to_generate"],
                return_output_log_probs=params["logprobs"],
                top_k_sampling=params["top_k"],
                top_p_sampling=params["top_p"],
                temperature=params["temperature"],
                add_BOS=params["add_BOS"],
                stop_on_double_eol=params["stop_on_double_eol"],
                stop_on_eol=params["stop_on_eol"],
                use_eod_token_for_early_termination=params[
                    "use_eod_token_for_early_termination"],
                random_seed=params["random_seed"],
                priority=params["priority"],
                ttft_deadline_ms=params["ttft_deadline_ms"],
                tpot_deadline_ms=params["tpot_deadline_ms"],
                trace_id=trace_id, **params["blocks"])
        except EngineOverloaded as eo:
            return 503, {"error": str(eo),
                         "retry_after": getattr(eo, "retry_after", 1.0),
                         **getattr(eo, "info", {})}
        except RequestShed as rs:
            return 503, {"error": str(rs), "shed": True,
                         "retry_after": getattr(rs, "retry_after", 1.0)}
        except InvalidRequest as ve:
            return 400, {"error": str(ve.args[0] if ve.args else ve)}
        first = q.next_event(timeout=600.0)
        if first is None:
            q.abandon()
            return 500, {"error": "stream produced no event within 600s"}
        if first.kind == "error":
            # terminal before any byte was written: still a buffered
            # answer — shed stays retryable (503), failure is a 500
            data = first.data
            if data.get("shed"):
                return 503, {"error": data.get("error", "request shed"),
                             "shed": True,
                             "retry_after": data.get("retry_after", 1.0)}
            return 500, {"error": data.get("error", "generation failed")}
        headers = {"X-MLT-Trace-Id": trace_id} if trace_id else {}
        ttft = req.ttft
        if ttft is not None:
            headers["X-MLT-TTFT-S"] = str(round(ttft, 6))
        try:
            handler._begin(200, SSE_CONTENT_TYPE, headers)
            if first.kind == "token":
                handler._send_chunk(token_frame([first], self._detokenize))
                # flight-record event: the instant the first token
                # actually left for the client
                req._flight.event("first_byte_flushed")
                attached = self._stream_writer.attach(q, handler.connection)
                ended = q.wait_terminal(gap_timeout=600.0)
                rest = self._stream_writer.detach(attached)
                if rest:
                    handler._send_chunk(rest)
                if not ended:
                    handler._send_chunk(sse_encode("error", {
                        "error": "stream stalled (no event within 600s)"}))
                    return None
                events = q.iter_events(timeout=0.0)
            else:
                events = [first]
            # the stream has ended: nothing below waits
            for ev in events:
                if ev.kind == "token":  # applied since the writer's last pass
                    handler._send_chunk(token_frame([ev], self._detokenize))
                elif ev.kind == "done":
                    if ev.data.get("dropped_events"):
                        # honest drop-to-terminal: the incremental
                        # events above are incomplete, the done body
                        # below is not
                        handler._send_chunk(sse_encode("dropped", {
                            "dropped_events": ev.data["dropped_events"]}))
                    texts, segments, log_probs = eng.finalize_stream_request(
                        req, return_output_log_probs=params["logprobs"])
                    body = {"text": texts, "segments": segments,
                            "logprobs": log_probs}
                    if trace_id:
                        timing = self.request_timing(trace_id)
                        if timing is not None:
                            body["timing"] = timing
                    handler._send_chunk(sse_encode("done", body))
                else:  # terminal error after bytes were written:
                    # structured SSE error frame, never silent truncation
                    data = dict(ev.data)
                    data.setdefault("error", "generation failed")
                    handler._send_chunk(sse_encode("error", data))
            return None
        except (BrokenPipeError, ConnectionError, OSError):
            # client went away mid-stream: shed future publishes and let
            # the generation finish on its own (it may be shared work)
            q.abandon()
            return None
        finally:
            if obs_registry.publishing():
                self._m_stream_requests.inc()
                self._m_stream_wakeups.inc(q.wakeups)

    def _make_handler(server):  # noqa: N805 — `server` is the enclosing object
        class Handler(BaseHTTPRequestHandler):
            def _begin(self, code: int, content_type="application/json",
                       headers=None, length: Optional[int] = None):
                """THE write-path entry for buffered AND streamed
                responses: status line + headers.  A streamed response
                (``length=None``) carries no Content-Length — the body
                is delimited by EOF (HTTP/1.0 semantics) — and disables
                Nagle coalescing so each flushed SSE frame hits the wire
                immediately instead of waiting out the delayed-ACK timer
                (first-byte latency is the whole point of streaming)."""
                if length is None:
                    self.connection.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                if length is not None:
                    self.send_header("Content-Length", str(length))
                else:
                    self.send_header("Connection", "close")
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()

            def _send(self, code: int, body, content_type="application/json",
                      headers=None):
                data = (json.dumps(body) if content_type == "application/json"
                        else body).encode()
                self._begin(code, content_type, headers, length=len(data))
                with obs_trace.span("serve-write"):
                    self.wfile.write(data)

            def _send_chunk(self, data: bytes):
                """One streamed body write, flushed to the socket."""
                with obs_trace.span("serve-write"):
                    self.wfile.write(data)
                    self.wfile.flush()

            def do_PUT(self):
                if self.path.rstrip("/") != "/api":
                    return self._send(404, {"error": "not found"})
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError):
                    return self._send(400, {"error": "invalid JSON"})
                # distributed tracing (ISSUE 12): accept the caller's /
                # router's trace id, mint one otherwise; every response
                # echoes it so untraced callers can still correlate
                trace_id = (self.headers.get("X-MLT-Trace-Id", "").strip()
                            or uuid.uuid4().hex)
                try:
                    if isinstance(payload, dict) and payload.get("stream"):
                        # SSE path; a None return means the stream was
                        # served (headers + body already written), else
                        # fall through to the buffered answer below
                        with obs_trace.span("serve-api-stream",
                                            trace_id=trace_id):
                            fallback = server.stream_response(
                                self, payload, trace_id=trace_id)
                        if fallback is None:
                            return
                        code, body = fallback
                    else:
                        with obs_trace.span("serve-api", trace_id=trace_id):
                            code, body = server.handle_request(
                                payload, trace_id=trace_id)
                except Exception as e:  # last-resort: still a JSON answer
                    code, body = 500, {
                        "error": f"internal error: {type(e).__name__}: {e}"}
                if isinstance(body, str):  # legacy engines may return text
                    return self._send(code, body, "text/plain")
                headers = {"X-MLT-Trace-Id": trace_id}
                if code == 503 and isinstance(body, dict) \
                        and "retry_after" in body:
                    headers["Retry-After"] = str(
                        max(1, int(body["retry_after"])))
                if code == 200 and isinstance(body, dict) \
                        and body.get("timing", {}).get("ttft_s") is not None:
                    # server-side first-token seconds as a header, so the
                    # router's TTFT metric never has to parse the body
                    headers["X-MLT-TTFT-S"] = str(body["timing"]["ttft_s"])
                return self._send(code, body, headers=headers)

            def do_POST(self):
                # replica admin plane (ISSUE 19): the cross-replica KV
                # push lands here as raw octet-stream; everything else
                # keeps the reference's PUT semantics (POST /api works
                # as a convenience; reference is PUT-only)
                if self.path.rstrip("/") == "/admin/kv_push":
                    try:
                        length = int(self.headers.get("Content-Length", 0))
                    except ValueError:
                        return self._send(
                            400, {"error": "invalid Content-Length"})
                    blob = self.rfile.read(length)
                    trace_id = (self.headers.get("X-MLT-Trace-Id", "").strip()
                                or uuid.uuid4().hex)
                    try:
                        code, body = server.kv_push(
                            blob, trace_id=trace_id)
                    except Exception as e:
                        code, body = 500, {
                            "error":
                            f"internal error: {type(e).__name__}: {e}"}
                    headers = {"X-MLT-Trace-Id": trace_id}
                    if code == 503 and isinstance(body, dict) \
                            and "retry_after" in body:
                        headers["Retry-After"] = str(
                            max(1, int(body["retry_after"])))
                    return self._send(code, body, headers=headers)
                return self.do_PUT()

            def do_GET(self):
                path, _, query = self.path.partition("?")
                path = path.rstrip("/")
                if path == "/health":
                    return self._send(200, server.health())
                if path == "/metrics":
                    # Prometheus exposition (observability/registry.py),
                    # alongside /health on the same port — the serving
                    # analog of pretrain's --metrics_port endpoint
                    return self._send(
                        200, server.metrics_text(),
                        "text/plain; version=0.0.4; charset=utf-8")
                if path == "/profile":
                    return self._send(*server.profile(parse_qs(query)))
                if path == "/debug/requests":
                    # recent flight records (observability/flight.py):
                    # ?n= caps the count, ?trace_id= filters.  Schema:
                    # docs/guide/observability.md "Request tracing"
                    qs = parse_qs(query)
                    try:
                        n = int(qs["n"][0]) if "n" in qs else None
                    except ValueError:
                        return self._send(
                            400, {"error": "n must be an integer"})
                    tid = qs.get("trace_id", [None])[0]
                    return self._send(
                        200, server.debug_requests(n=n, trace_id=tid))
                index = _STATIC_DIR / "index.html"
                if self.path in ("/", "/index.html") and index.exists():
                    return self._send(200, index.read_text(), "text/html")
                return self._send(404, {"error": "not found"})

            def log_message(self, fmt, *args):  # quiet by default
                pass

        return Handler

    def health(self) -> dict:
        """Liveness + replica identity + engine occupancy + prefix-cache
        state (continuous-batching engines only).  The full payload schema
        lives in docs/guide/serving.md ("/health payload") — keep the two
        in sync; the router's ReplicaView (serving/router/registry.py) is
        the consumer."""
        with self._seq_lock:
            self._health_seq += 1
            seq = self._health_seq
        info = {
            "status": "ok",
            "batching": self.batching,
            # streaming capability + elastic-discovery mode (ISSUE 18):
            # the router's ReplicaView parses both, so a fleet can tell
            # which replicas serve "stream": true and which arrived via
            # /admin/register heartbeats rather than static config
            "streaming": bool(self.batching
                              and hasattr(self.engine, "submit_stream")),
            "registered": self.register_url is not None,
            # disaggregated serving (ISSUE 19): the advertised role the
            # router's disagg policy steers by; "unified" replicas serve
            # both phases (the pre-disagg behavior, byte for byte)
            "role": self.role,
            "replica_id": self.replica_id,
            "seq": seq,
            "uptime_s": round(time.monotonic() - self._t_start, 3),
        }
        eng = self.engine
        if self.batching:
            with eng._lock:
                cache = getattr(eng, "cache", None)
                info.update(
                    active_slots=sum(r is not None for r in eng._slots),
                    peak_active_slots=eng.peak_active_slots,
                    max_slots=eng.max_slots,
                    queued=len(eng._queue),
                    prefilling=sum(
                        r is not None and r._phase == "prefill"
                        for r in eng._slots),
                    free_pages=eng.pool.num_free,
                    total_pages=eng.pool.num_pages - 1,
                    pages_cached=len(cache) if cache is not None else 0,
                    available_pages=eng.pool.num_available,
                    prefix_hit_tokens=eng.prefix_hit_tokens,
                    prefix_miss_tokens=eng.prefix_miss_tokens,
                    ticks=eng.ticks,
                    engine_failures=eng.failures,
                    page_size=eng.page_size,
                    # quantized paged KV (ISSUE 13): storage mode + byte
                    # budget, so the router can route capacity-aware in
                    # bytes rather than pages of unknown width
                    kv_dtype=getattr(eng, "kv_dtype", "bf16"),
                    kv_pool_bytes=eng.pool.kv_pool_bytes(),
                    kv_scale_bytes=eng.pool.kv_scale_bytes(),
                )
            mesh = getattr(eng, "mesh", None)
            info["mesh"] = ({str(k): int(v) for k, v in dict(mesh.shape).items()}
                            if mesh is not None else {})
            info["tp"] = getattr(eng, "_tp", 1)
            # pipeline-parallel serving (ISSUE 20): stage count of the
            # compiled tick; "stages" aliases "pp" for dashboards that
            # speak stage language.  1 = flat TP-only replica.
            info["pp"] = getattr(eng, "_pp", 1)
            info["stages"] = getattr(eng, "_pp", 1)
            info["kv_stage_bytes"] = eng.pool.kv_stage_bytes()
            if hasattr(eng, "scheduler_stats"):
                # control-plane view: policy, per-priority queue depths,
                # preemption/shed/deadline-miss totals, drain EMAs
                info["scheduler"] = eng.scheduler_stats()
            if hasattr(eng, "spec_stats"):
                # speculative decoding: depth cap, acceptance rate,
                # tokens per tick (generation/speculative/)
                info["spec"] = eng.spec_stats()
        return info

    def request_timing(self, trace_id: str) -> Optional[dict]:
        """Server-side timing block for a 200 response, read from the
        engine's flight records for ``trace_id`` (one per prompt in the
        request): the real first-token time (the minimum across prompts
        — the instant the response started existing) and the matching
        latency decomposition.  None when the recorder is off or the
        records already aged out of the ring."""
        flight = getattr(self.engine, "flight", None)
        if flight is None or not flight.enabled:
            return None
        recs = flight.lookup(trace_id)
        if not recs:
            return None
        with_ttft = [r for r in recs if r.get("ttft_s") is not None]
        first = (min(with_ttft, key=lambda r: r["ttft_s"])
                 if with_ttft else None)
        timing = {
            "trace_id": trace_id,
            "replica_id": self.replica_id,
            "requests": len(recs),
            "ttft_s": first["ttft_s"] if first else None,
            "latency_s": max((r["latency_s"] or 0.0) for r in recs),
        }
        if first is not None and "ttft_decomposition" in first:
            timing["ttft_decomposition"] = first["ttft_decomposition"]
        return timing

    def debug_requests(self, n: Optional[int] = None,
                       trace_id: Optional[str] = None) -> dict:
        """``GET /debug/requests``: recent flight records as JSON (in-
        flight first, then retired newest-first), plus replica identity
        so a fleet aggregation stays attributable."""
        flight = getattr(self.engine, "flight", None)
        enabled = flight is not None and flight.enabled
        recs = flight.snapshot(n=n, trace_id=trace_id) if enabled else []
        return {
            "replica_id": self.replica_id,
            "flight_recorder": enabled,
            "count": len(recs),
            "requests": recs,
        }

    def profile(self, qs: dict):
        """``GET /profile?ticks=N``: arm a ``jax.profiler`` window of the
        live engine's next N ticks (observability/profiler.py; the
        engine's loop starts and stops it at step boundaries, output
        under ``--profile_dir``, at most ``--profile_max_captures`` a
        process).  The capture holds the engine-* and serve-* spans on
        the device planes' clock.  N is held to ``MAX_PROFILE_TICKS``,
        and a window that outlives the traffic ends when the engine goes
        idle.  Returns (status, body): 409 while one is pending or active
        or the budget is spent, 503 on an engine with no scheduler loop."""
        trig = getattr(self.engine, "profile_trigger", None)
        if trig is None:
            return 503, {"error": "this engine takes no profile"}
        try:
            ticks = int(qs["ticks"][0]) if "ticks" in qs else None
        except ValueError:
            return 400, {"error": "ticks must be an integer"}
        if ticks is not None:
            ticks = min(ticks, MAX_PROFILE_TICKS)
        res = trig.request(ticks)
        return (200 if res.get("accepted") else 409), res

    def metrics_text(self) -> str:
        """Prometheus text for GET /metrics: refresh the engine-occupancy
        gauges from live engine state (scrape-time pull — the engine also
        pushes them per tick), then render the process-wide registry."""
        from megatron_llm_tpu.observability.registry import get_registry

        reg = get_registry()
        eng = self.engine
        if self.batching:
            with eng._lock:
                reg.gauge("mlt_engine_active_slots").set(
                    sum(r is not None for r in eng._slots))
                reg.gauge("mlt_engine_free_pages").set(eng.pool.num_free)
                reg.gauge("mlt_engine_max_slots").set(eng.max_slots)
                reg.gauge("mlt_engine_pool_pages").set(eng.pool.num_pages - 1)
                cache = getattr(eng, "cache", None)
                reg.gauge("mlt_engine_pages_cached").set(
                    len(cache) if cache is not None else 0)
                # queue-depth gauges (total + per-priority) have ONE owner:
                # the engine's scheduler update point
                eng._publish_queued_locked(force=True)
        return reg.render()

    def _start_engine(self):
        if self.batching and hasattr(self.engine, "start"):
            self._stream_writer.start()
            self.engine.stream_kick = self._stream_writer.kick
            self.engine.start()  # background scheduler drives shared ticks

    # ---- elastic discovery (ISSUE 18) -----------------------------------

    def _heartbeat_loop(self, advertised: str) -> None:
        """POST ``/admin/register`` to the router until stopped.  Every
        beat carries the advertised url + replica_id; failures are
        swallowed (the router may be down or restarting — the whole
        point of heartbeats is that it catches up on the next one)."""
        import urllib.request

        target = self.register_url.rstrip("/") + "/admin/register"
        body = json.dumps({"replica": advertised,
                           "replica_id": self.replica_id}).encode()
        while True:
            req = urllib.request.Request(
                target, data=body,
                headers={"Content-Type": "application/json"}, method="POST")
            try:
                with urllib.request.urlopen(req, timeout=5.0) as resp:
                    resp.read()
            except Exception:
                pass
            if self._register_stop.wait(self.register_interval_s):
                return

    def _start_heartbeat(self, port: int) -> None:
        if not self.register_url or self._register_thread is not None:
            return
        advertised = self.advertise_url or f"http://127.0.0.1:{port}"
        self._register_stop.clear()
        t = threading.Thread(target=self._heartbeat_loop, args=(advertised,),
                             name="replica-register", daemon=True)
        self._register_thread = t
        t.start()

    # ---- lifecycle ------------------------------------------------------

    def bind(self, host: str = "0.0.0.0", port: int = 5000) -> int:
        """Bind the listening socket (without serving) and return the bound
        port — with ``port=0`` the OS picks a free one, which is how local
        fleets (tests, bench_decode --mode router) avoid port races.  Call
        ``serve()`` afterwards to block."""
        install_compile_counter()  # mlt_jit_* on /metrics, the compile log
        with startup_phase("server-bind"):
            self._httpd = ThreadingHTTPServer((host, port),
                                              self._make_handler())
            # the stdlib listens with a backlog of 5 and the kernel resets
            # the connection that finds it full: a closed loop of 512
            # clients lost one request in 631 to that (PERF.md, PR 58)
            self._httpd.socket.listen(1024)
        return self._httpd.server_address[1]

    def serve(self):
        """Serve on the socket from ``bind()`` (blocking)."""
        assert self._httpd is not None, "call bind() first"
        self._start_engine()
        self._start_heartbeat(self._httpd.server_address[1])
        self._httpd.serve_forever()

    def run(self, host: str = "0.0.0.0", port: int = 5000):
        self.bind(host, port)
        self.serve()

    def start_background(self, host: str = "127.0.0.1", port: int = 5000):
        """Run in a daemon thread (used by tests); returns the bound port."""
        bound = self.bind(host, port)
        self._start_engine()
        self._start_heartbeat(bound)
        t = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        t.start()
        return bound

    def stop(self):
        self._register_stop.set()
        if self._register_thread is not None:
            self._register_thread.join(timeout=5.0)
            self._register_thread = None
        if self._httpd is not None:
            self._httpd.shutdown()
            # close the listening socket too: new connections must be
            # REFUSED (a router fails over on that), not sit in a backlog
            # nobody will ever accept
            self._httpd.server_close()
            self._httpd = None
        if self.batching and hasattr(self.engine, "stop"):
            self.engine.stop()
            self.engine.stream_kick = None
        self._stream_writer.stop()
