"""Stream event shapes + the SSE wire encoding shared by all three tiers.

The wire format is plain Server-Sent Events (one ``event:`` line, one
``data:`` line holding a JSON object, a blank line):

* ``event: token`` — ``{"tokens": [...], "text": "...", "logprobs":
  [...]}``: the tokens of one stream applied since its last frame, in
  order: usually one tick's, several when a verify tick retires
  several per flush or when the stream writer found the socket full at
  an earlier tick (``token_frame`` joins the queued events).
* ``event: dropped`` — ``{"dropped_events": n}``: the consumer fell
  behind the bounded emission queue and *incremental* events were shed;
  the terminal ``done`` body is still complete (drop-to-terminal).
* ``event: done`` — the full buffered-response body (``{"text",
  "segments", "logprobs", "timing"}``): byte-identical to what the same
  request would have returned with ``"stream": false``.
* ``event: error`` — ``{"error": msg, ...}``: structured terminal
  failure (engine error, shed, or mid-stream replica death at the
  router).  A well-formed stream ALWAYS ends in ``done`` or ``error``;
  an EOF without one is a truncation (``sse_scan_terminal``).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

__all__ = [
    "SSE_CONTENT_TYPE",
    "StreamEvent",
    "iter_sse_events",
    "parse_sse",
    "sse_encode",
    "sse_scan_terminal",
    "token_frame",
]

SSE_CONTENT_TYPE = "text/event-stream"

# terminal markers at line starts — ``data:`` payloads are single-line
# JSON (json.dumps escapes newlines), so a raw b"\nevent: " can only be
# a real SSE field line, never generated text
_TERMINAL_MARKERS = (b"\nevent: done\n", b"\nevent: error\n")
# longest marker, minus one: how much stream tail must be re-scanned so
# a marker split across two chunks is still seen
SSE_TAIL_KEEP = max(len(m) for m in _TERMINAL_MARKERS) - 1


@dataclasses.dataclass
class StreamEvent:
    """One emission-queue entry (engine tier; the SSE lines are the
    serialized form the replica tier writes)."""

    kind: str  # "token" | "done" | "error"
    tokens: List[int] = dataclasses.field(default_factory=list)
    log_probs: List[float] = dataclasses.field(default_factory=list)
    data: Dict = dataclasses.field(default_factory=dict)

    @property
    def terminal(self) -> bool:
        return self.kind in ("done", "error")


def sse_encode(event: str, data: dict) -> bytes:
    """One SSE frame: ``event:`` + single-line JSON ``data:`` + blank."""
    return (f"event: {event}\ndata: {json.dumps(data)}\n\n").encode()


def token_frame(events: Iterable[StreamEvent],
                detokenize: Optional[Callable[[List[int]], str]] = None
                ) -> bytes:
    """THE ``token`` frame of one stream: the tokens and log-probs of
    ``events`` joined in order (and their text, given a detokenizer)."""
    tokens: List[int] = []
    log_probs: List[float] = []
    for ev in events:
        tokens += ev.tokens
        log_probs += ev.log_probs
    frame = {"tokens": tokens, "logprobs": log_probs}
    if detokenize is not None:
        frame["text"] = detokenize(tokens)
    return sse_encode("token", frame)


def sse_scan_terminal(tail: bytes, chunk: bytes) -> Tuple[bool, bytes]:
    """Incremental terminal detection for a pass-through proxy.

    Feed each forwarded chunk with the ``tail`` returned by the previous
    call (start with ``b"\\n"`` so a marker at byte 0 matches); returns
    ``(saw_terminal, new_tail)``.  Once a terminal frame has been seen
    the stream may legally EOF; an EOF before that is a truncation."""
    buf = tail + chunk
    seen = any(m in buf for m in _TERMINAL_MARKERS)
    return seen, buf[-SSE_TAIL_KEEP:] if len(buf) > SSE_TAIL_KEEP else buf


def parse_sse(raw: bytes) -> List[Tuple[str, dict]]:
    """Decode a complete SSE byte stream into ``(event, data)`` pairs —
    the client-side helper tests and bench_decode use.  Frames with
    undecodable data become ``(event, {"raw": ...})`` rather than
    raising: a truncated final frame must not mask the truncation."""
    out: List[Tuple[str, dict]] = []
    for frame in raw.split(b"\n\n"):
        if not frame.strip():
            continue
        event, data = "message", None
        for line in frame.split(b"\n"):
            if line.startswith(b"event: "):
                event = line[len(b"event: "):].decode(errors="replace")
            elif line.startswith(b"data: "):
                try:
                    data = json.loads(line[len(b"data: "):])
                except ValueError:
                    data = {"raw": line[len(b"data: "):].decode(
                        errors="replace")}
        out.append((event, data if isinstance(data, dict) else {}))
    return out


def iter_sse_events(chunks: Iterable[bytes]) -> Iterator[Tuple[str, dict]]:
    """Incremental variant of :func:`parse_sse`: yields each complete
    frame as soon as its blank-line delimiter arrives (what a live
    streaming client wants; ``parse_sse`` needs the whole body)."""
    buf = b""
    for chunk in chunks:
        buf += chunk
        while b"\n\n" in buf:
            frame, _, buf = buf.partition(b"\n\n")
            for pair in parse_sse(frame + b"\n\n"):
                yield pair
    if buf.strip():
        for pair in parse_sse(buf):
            yield pair
