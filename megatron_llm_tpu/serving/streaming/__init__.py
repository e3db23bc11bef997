"""Token streaming: the per-request emission path from engine to client.

Three tiers share this package's wire shapes (ISSUE 18):

* **Engine tier** — ``ContinuousBatchingEngine.submit_stream`` attaches a
  :class:`StreamQueue` to the request at enqueue time; the tick's and
  the verify tick's apply paths publish freshly-retired token batches
  into it under the engine lock, and ``_retire``/``_fail_locked``/
  ``_shed_locked`` publish the terminal event (carrying the flight-record
  timing payload).  The queue is bounded and never blocks the publisher:
  a slow consumer loses *incremental* events (counted, surfaced in the
  terminal event) but always receives the terminal — drop-to-terminal,
  never engine backpressure.
* **Replica tier** — ``MegatronServer`` turns the queue into an SSE
  response for ``"stream": true`` requests (``event: token`` per batch,
  ``event: done`` carrying the exact buffered-response body, ``event:
  error`` on failure), flushing the first byte the moment the
  ``X-MLT-TTFT-S`` stamp says the token existed.
* **Router tier** — ``ForwardingProxy.forward_stream`` pumps the bytes
  through verbatim, failing over only during the connect phase and
  replacing a mid-stream replica death with a structured terminal
  ``error`` event (``sse_scan_terminal`` is how it knows a stream ended
  without one).

Guide: docs/guide/serving.md "Streaming".
"""

from megatron_llm_tpu.serving.streaming.events import (  # noqa: F401
    SSE_CONTENT_TYPE,
    StreamEvent,
    parse_sse,
    sse_encode,
    sse_scan_terminal,
    token_frame,
)
from megatron_llm_tpu.serving.streaming.queue import StreamQueue  # noqa: F401
from megatron_llm_tpu.serving.streaming.writer import StreamWriter  # noqa: F401
