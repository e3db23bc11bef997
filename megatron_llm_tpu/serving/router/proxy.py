"""Forwarding proxy: the router's data plane.

Walks a policy's candidate list forwarding ``PUT /api`` bodies verbatim.
The failure semantics are the whole point:

* **Connect-phase failure** (refused / DNS / timeout before any response
  byte): the replica never saw a parseable request — safe to fail over.
  The failure is reported into the registry breaker
  (``record_forward_failure``) so the data plane ejects a dead replica
  without waiting for the next poll tick, and the replica is excluded
  for the remainder of THIS request.
* **Response-phase failure** (status line received, then the body dies):
  the replica may have executed the generation — a retry would re-run a
  non-idempotent request (burn pages/compute, and for seeded sampling
  produce a second stream).  Never retried: surfaced as a structured 502.
* **503 from a replica** (EngineOverloaded / RequestShed): honored, not
  hammered — the replica's ``Retry-After`` is recorded, the proxy tries
  the next candidate, and only when every candidate is saturated does it
  back off (bounded by ``max_retries`` rounds, sleeping the fleet-minimum
  Retry-After capped at ``backoff_cap_s``) before re-walking the 503'd
  replicas.  Exhaustion returns an aggregated 503 whose Retry-After is
  the fleet minimum.
* **4xx / 200**: terminal either way — forwarded verbatim (a validation
  error on replica A is a validation error on replica B too).
"""

from __future__ import annotations

import http.client
import json
import socket
import time
import urllib.error
import urllib.request
from typing import Callable, List, Optional, Sequence, Tuple

from megatron_llm_tpu.serving.router.registry import ReplicaRegistry
from megatron_llm_tpu.serving.streaming import sse_encode, sse_scan_terminal

__all__ = ["ForwardOutcome", "ForwardingProxy", "StreamHandle"]


class ForwardOutcome:
    """What the router handler needs to answer the client: status, JSON-
    encodable body (or raw bytes), optional Retry-After, the replica that
    answered, and the failure trail for observability."""

    def __init__(self, status: int, body: bytes,
                 replica_url: Optional[str] = None,
                 retry_after: Optional[float] = None,
                 attempts: int = 1,
                 failovers: int = 0,
                 retries: int = 0,
                 ttft_s: Optional[float] = None):
        self.status = status
        self.body = body
        self.replica_url = replica_url
        self.retry_after = retry_after
        self.attempts = attempts
        self.failovers = failovers
        self.retries = retries
        # replica-reported first-token seconds (X-MLT-TTFT-S): the
        # honest TTFT signal; None from pre-tracing replicas
        self.ttft_s = ttft_s


def _err_body(msg: str, **extra) -> bytes:
    return json.dumps({"error": msg, **extra}).encode()


class StreamHandle:
    """An ACCEPTED upstream stream (ISSUE 18): the replica's status line
    and headers arrived — for a streaming replica that means the first
    token exists — but the body is unread.  From this point on the
    request is committed to this replica: ``pump_stream`` relays the
    body and mid-stream death becomes a structured terminal SSE error
    event, never a retry (the never-retry-mid-body rule) and never a
    silent truncation."""

    def __init__(self, resp, url: str, *, content_type: str,
                 ttft_s: Optional[float], attempts: int, failovers: int,
                 retries: int):
        self.resp = resp  # open http response, body unread
        self.url = url
        self.content_type = content_type
        self.ttft_s = ttft_s  # the replica's X-MLT-TTFT-S stamp
        self.attempts = attempts
        self.failovers = failovers
        self.retries = retries


class ForwardingProxy:
    """Forward one request body along a candidate list (see module doc)."""

    def __init__(self, registry: ReplicaRegistry, *,
                 timeout_s: float = 300.0,
                 max_retries: int = 2,
                 backoff_cap_s: float = 5.0,
                 sleep: Callable[[float], None] = time.sleep):
        self.registry = registry
        self.timeout_s = timeout_s
        self.max_retries = max_retries
        self.backoff_cap_s = backoff_cap_s
        self._sleep = sleep  # injectable so tests don't wall-clock wait

    # ---- single attempt -------------------------------------------------

    def _connect(self, url: str, body: bytes,
                 headers: Optional[dict] = None):
        """The connect phase shared by buffered and streamed forwards:
        send the request, classify everything up to (and including) the
        status line + headers.  Returns (kind, status, error_body,
        retry_after, resp): ``resp`` is the OPEN response (body unread)
        iff the replica accepted — every other kind is a pre-body
        failure ('overloaded'/'terminal'/'partial'/'connect_fail') and
        is safe to fail over or forward verbatim."""
        hdrs = {"Content-Type": "application/json"}
        hdrs.update(headers or {})
        req = urllib.request.Request(
            url.rstrip("/") + "/api", data=body,
            headers=hdrs, method="PUT")
        try:
            resp = urllib.request.urlopen(req, timeout=self.timeout_s)
        except urllib.error.HTTPError as e:
            # a status line arrived — the replica spoke; read its body
            # (itself a response-phase read that may die)
            try:
                data = e.read()
            except Exception:
                return ("partial", 502,
                        _err_body(f"replica {url} dropped mid-error-body"),
                        None, None)
            if e.code == 503:
                ra = e.headers.get("Retry-After")
                try:
                    retry_after = float(ra) if ra is not None else None
                except ValueError:
                    retry_after = None
                if retry_after is None:
                    try:
                        retry_after = float(
                            json.loads(data).get("retry_after", 1.0))
                    except (ValueError, AttributeError):
                        retry_after = 1.0
                return ("overloaded", 503, data, retry_after, None)
            return ("terminal", e.code, data, None, None)
        except (urllib.error.URLError, socket.timeout, ConnectionError,
                OSError) as e:
            # no status line: the request never started executing
            return ("connect_fail", 0,
                    _err_body(f"{type(e).__name__}: {e}"), None, None)
        return ("accepted", resp.status, b"", None, resp)

    def _attempt(self, url: str, body: bytes,
                 headers: Optional[dict] = None
                 ) -> Tuple[str, int, bytes, Optional[float],
                            Optional[float]]:
        """One forward to one replica.

        Returns (kind, status, body, retry_after, ttft_s) with kind in
        {'ok', 'overloaded', 'terminal', 'connect_fail', 'partial'};
        ``headers`` (the trace-id propagation path) merge into the
        forwarded request, and ``ttft_s`` is the replica's own
        ``X-MLT-TTFT-S`` first-token stamp when it sent one."""
        kind, status, payload, ra, resp = self._connect(url, body, headers)
        if resp is None:
            return (kind, status, payload, ra, None)
        with resp:
            try:
                data = resp.read()
            except (http.client.IncompleteRead, ConnectionError,
                    socket.timeout, OSError) as e:
                # response-phase death AFTER the replica accepted the
                # request: non-idempotent, never retried (module doc)
                return ("partial", 502,
                        _err_body(
                            f"replica {url} dropped mid-response "
                            f"({type(e).__name__}); not retried — the "
                            f"generation may have executed"), None, None)
            try:
                ttft = float(resp.headers.get("X-MLT-TTFT-S"))
            except (TypeError, ValueError):
                ttft = None
            return ("ok", resp.status, data, None, ttft)

    # ---- candidate walk -------------------------------------------------

    def forward(self, candidate_urls: Sequence[str], body: bytes,
                headers: Optional[dict] = None) -> ForwardOutcome:
        """Walk candidates with failover, then bounded Retry-After-honoring
        retry rounds over the saturated ones.  ``headers`` ride every
        attempt (trace-id propagation: the router's ``X-MLT-Trace-Id``
        reaches whichever replica finally serves the request)."""
        from megatron_llm_tpu.observability.trace import span

        trace_id = (headers or {}).get("X-MLT-Trace-Id", "")
        excluded: set = set()   # connect-failed: out for this request
        attempts = failovers = retries = 0
        saturated: List[Tuple[str, float]] = []
        last_503: Optional[Tuple[bytes, float]] = None

        def walk(urls: Sequence[str]) -> Optional[ForwardOutcome]:
            nonlocal attempts, failovers, last_503
            saturated.clear()
            for url in urls:
                if url in excluded:
                    continue
                attempts += 1
                with span("router-forward", url=url, trace_id=trace_id):
                    kind, status, data, ra, ttft = self._attempt(
                        url, body, headers)
                if kind == "ok" or kind == "terminal":
                    return ForwardOutcome(
                        status, data, replica_url=url, attempts=attempts,
                        failovers=failovers, retries=retries,
                        ttft_s=ttft)
                if kind == "partial":
                    return ForwardOutcome(
                        status, data, replica_url=url, attempts=attempts,
                        failovers=failovers, retries=retries)
                if kind == "connect_fail":
                    excluded.add(url)
                    failovers += 1
                    self.registry.record_forward_failure(
                        url, data.decode(errors="replace"))
                    continue
                # overloaded: remember for the retry rounds
                saturated.append((url, ra if ra is not None else 1.0))
                last_503 = (data, ra if ra is not None else 1.0)
            return None

        out = walk(candidate_urls)
        rounds = 0
        while out is None and saturated and rounds < self.max_retries:
            rounds += 1
            retries += 1
            # honor the fleet-minimum Retry-After (bounded: a router thread
            # sleeping 60s per 503 would be its own outage)
            self._sleep(min(min(ra for _, ra in saturated),
                            self.backoff_cap_s))
            out = walk([u for u, _ in saturated])
        if out is not None:
            return out
        if last_503 is not None:
            data, ra = last_503
            if saturated:  # aggregate: the soonest any replica reopens
                ra = min(r for _, r in saturated)
            try:
                parsed = json.loads(data)
            except ValueError:
                parsed = {"error": "fleet saturated"}
            parsed.setdefault("error", "fleet saturated")
            parsed["fleet_saturated"] = True
            return ForwardOutcome(
                503, json.dumps(parsed).encode(), retry_after=ra,
                attempts=attempts, failovers=failovers, retries=retries)
        return ForwardOutcome(
            502, _err_body("no replica reachable",
                           tried=list(dict.fromkeys(candidate_urls))),
            attempts=attempts, failovers=failovers, retries=retries)

    # ---- streaming pass-through (ISSUE 18) ------------------------------

    def forward_stream(self, candidate_urls: Sequence[str], body: bytes,
                       headers: Optional[dict] = None):
        """Connect phase of a streamed forward: exactly ``forward``'s
        failure semantics — fail over on connect failure, bounded
        Retry-After rounds over saturated replicas, terminal 4xx
        forwarded verbatim — but a replica that ACCEPTS (status line +
        headers, i.e. its first token exists) returns an open
        :class:`StreamHandle` instead of a read body.  From that point
        ``pump_stream`` owns the never-retry-mid-body rule."""
        excluded: set = set()
        attempts = failovers = retries = 0
        saturated: List[Tuple[str, float]] = []
        last_503: Optional[Tuple[bytes, float]] = None

        def walk(urls: Sequence[str]):
            nonlocal attempts, failovers, last_503
            saturated.clear()
            for url in urls:
                if url in excluded:
                    continue
                attempts += 1
                kind, status, payload, ra, resp = self._connect(
                    url, body, headers)
                if kind == "accepted":
                    try:
                        ttft = float(resp.headers.get("X-MLT-TTFT-S"))
                    except (TypeError, ValueError):
                        ttft = None
                    return StreamHandle(
                        resp, url,
                        content_type=resp.headers.get(
                            "Content-Type", "text/event-stream"),
                        ttft_s=ttft, attempts=attempts,
                        failovers=failovers, retries=retries)
                if kind in ("terminal", "partial"):
                    return ForwardOutcome(
                        status, payload, replica_url=url, attempts=attempts,
                        failovers=failovers, retries=retries)
                if kind == "connect_fail":
                    excluded.add(url)
                    failovers += 1
                    self.registry.record_forward_failure(
                        url, payload.decode(errors="replace"))
                    continue
                saturated.append((url, ra if ra is not None else 1.0))
                last_503 = (payload, ra if ra is not None else 1.0)
            return None

        out = walk(candidate_urls)
        rounds = 0
        while out is None and saturated and rounds < self.max_retries:
            rounds += 1
            retries += 1
            self._sleep(min(min(ra for _, ra in saturated),
                            self.backoff_cap_s))
            out = walk([u for u, _ in saturated])
        if out is not None:
            return out
        if last_503 is not None:
            data, ra = last_503
            if saturated:
                ra = min(r for _, r in saturated)
            try:
                parsed = json.loads(data)
            except ValueError:
                parsed = {"error": "fleet saturated"}
            parsed.setdefault("error", "fleet saturated")
            parsed["fleet_saturated"] = True
            return ForwardOutcome(
                503, json.dumps(parsed).encode(), retry_after=ra,
                attempts=attempts, failovers=failovers, retries=retries)
        return ForwardOutcome(
            502, _err_body("no replica reachable",
                           tried=list(dict.fromkeys(candidate_urls))),
            attempts=attempts, failovers=failovers, retries=retries)

    def pump_stream(self, handle: StreamHandle,
                    write: Callable[[bytes], None]) -> dict:
        """Relay an accepted stream's body to ``write`` (the router
        handler's flushing chunk writer), enforcing the two streamed
        response-phase guarantees:

        * never retried — the generation is executing on ``handle.url``;
        * never silently truncated — an SSE stream must end in a
          terminal ``done``/``error`` frame (``sse_scan_terminal``
          watches the forwarded bytes), so an upstream death or an EOF
          without one is replaced by a structured terminal ``error``
          frame and reported into the breaker.

        Returns ``{"bytes", "truncated", "error", "client_gone"}``."""
        resp = handle.resp
        is_sse = handle.content_type.startswith("text/event-stream")
        tail = b"\n"
        terminal_seen = not is_sse  # only SSE promises a terminal frame
        n = 0
        error = None
        with resp:
            while True:
                try:
                    chunk = resp.read1(65536)
                except (http.client.IncompleteRead, ConnectionError,
                        socket.timeout, OSError) as e:
                    error = f"{type(e).__name__}: {e}"
                    break
                if not chunk:
                    break
                if not terminal_seen:
                    terminal_seen, tail = sse_scan_terminal(tail, chunk)
                try:
                    write(chunk)
                except OSError:
                    # the CLIENT went away: stop reading, but the
                    # replica did nothing wrong — no breaker record
                    return {"bytes": n, "truncated": False,
                            "error": "client disconnected",
                            "client_gone": True}
                n += len(chunk)
        truncated = error is not None or not terminal_seen
        if truncated:
            self.registry.record_forward_failure(
                handle.url,
                error or f"replica {handle.url} closed its stream "
                         f"without a terminal event")
            if is_sse:
                try:
                    write(sse_encode("error", {
                        "error": f"replica {handle.url} died mid-stream; "
                                 f"not retried — the generation may have "
                                 f"executed",
                        "replica": handle.url,
                        "truncated": True}))
                except OSError:
                    pass  # client is gone too; nothing left to tell
        return {"bytes": n, "truncated": truncated, "error": error,
                "client_gone": False}
