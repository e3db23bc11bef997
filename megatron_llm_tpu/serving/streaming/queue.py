"""StreamQueue: the bounded per-request emission queue (engine tier).

One queue per ``submit_stream`` request.  The publisher side
(``publish_tokens`` / ``publish_terminal``) is called by the engine's
apply/retire paths WHILE HOLDING ``ContinuousBatchingEngine._lock`` — so
it must never block and never acquire anything beyond this queue's own
leaf lock (lock-order edge ``ContinuousBatchingEngine._lock ->
StreamQueue._lock``, committed in tools/graftcheck/lockorder.json; the
same discipline as the engine→FlightRecorder edge).

Overflow policy is drop-to-terminal: a consumer that falls behind the
bounded queue loses *incremental* token events (counted, reported in the
terminal event's ``dropped_events``), but the terminal event is always
accepted — the tick loop never waits on a slow HTTP client, and the
client always learns how the request ended.

A publish wakes a thread only if one is blocked in ``next_event`` (an
in-process consumer, or an HTTP handler waiting for its first token).
A served stream's later events are taken, without a wake-up a stream, by
the one stream writer (``serving/streaming/writer.py``: ``take_tokens``
once a tick) while the handler is parked in ``wait_terminal``.  One
consumer reads a queue at a time; both read through the same ordering
rule (``_pop_locked``) and the same bound.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Iterator, List, Optional, Sequence

from megatron_llm_tpu.serving.streaming.events import StreamEvent

__all__ = ["StreamQueue"]


class StreamQueue:
    """Bounded single-producer event queue, one consumer at a time."""

    def __init__(self, maxsize: int = 256):
        assert maxsize >= 1
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._ready = threading.Condition(self._lock)
        self._events = collections.deque()  # guarded by _lock
        self._terminal: Optional[StreamEvent] = None  # guarded by _lock
        self._terminal_taken = False  # guarded by _lock
        self._dropped = 0  # incremental events shed — guarded by _lock
        self._abandoned = False  # consumer gone — guarded by _lock
        self._readers = 0  # threads blocked in next_event — guarded by _lock
        self._published = 0  # publish_tokens calls so far — guarded by _lock
        self._wakeups = 0  # returns from a blocking wait — guarded by _lock

    # ---- publisher side (engine, holding its own _lock) -----------------
    # Method names are deliberately unique repo-wide (not `put`/`close`):
    # the engine reaches the queue through an untyped `req._stream`, so
    # graftcheck's lock-order pass resolves these calls by name.

    def publish_tokens(self, tokens: Sequence[int],
                       log_probs: Optional[Sequence[float]] = None) -> int:
        """Append one incremental token batch; NEVER blocks.  Returns the
        number of events shed by this call (0 or 1) so the engine can
        bump ``mlt_engine_stream_dropped_events_total``."""
        with self._ready:
            if self._terminal is not None:
                return 1  # post-terminal publish: late, count as shed
            self._published += 1
            if self._abandoned or len(self._events) >= self.maxsize:
                self._dropped += 1
                return 1
            self._events.append(StreamEvent(
                "token", tokens=list(tokens),
                log_probs=list(log_probs or [])))
            if self._readers:
                # nobody is woken for a queue the stream writer drains:
                # its handler parks in wait_terminal, not in next_event
                self._ready.notify_all()
            return 0

    def publish_terminal(self, event: StreamEvent) -> None:
        """Deliver the terminal event; always accepted (first one wins).
        Stamps the running drop count into the event so the consumer can
        tell a complete incremental stream from a shed one."""
        assert event.terminal, event.kind
        with self._ready:
            if self._terminal is None:
                event.data.setdefault("dropped_events", self._dropped)
                self._terminal = event
            self._ready.notify_all()

    # ---- consumer side (HTTP handler, stream writer, bench client) ------

    def _pop_locked(self) -> Optional[StreamEvent]:  # holds _lock
        """THE ordering rule of every consumer: queued incremental events
        oldest first, then the terminal event exactly once; None while
        there is nothing to take (and for good once the queue is dry)."""
        if self._abandoned:
            return None  # abandon() dries the consumer
        if self._events:
            return self._events.popleft()
        if self._terminal is not None and not self._terminal_taken:
            self._terminal_taken = True
            return self._terminal
        return None

    def next_event(self, timeout: Optional[float] = None
                   ) -> Optional[StreamEvent]:
        """Block for the next event.  The terminal event is returned
        exactly once, after every queued incremental event; afterwards
        (or on timeout) returns None."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._ready:
            while True:
                ev = self._pop_locked()
                if ev is not None or self._abandoned or self._terminal_taken:
                    return ev
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None
                self._readers += 1
                try:
                    woken = self._ready.wait(remaining)
                finally:
                    self._readers -= 1
                self._wakeups += 1
                if not woken:
                    return None

    def take_tokens(self) -> List[StreamEvent]:
        """Every queued incremental event, oldest first, without blocking;
        never the terminal event (the stream writer's drain: what it
        takes in one call it may send as one frame)."""
        with self._lock:
            taken = []
            while self._events:
                taken.append(self._pop_locked())
            return taken

    def wait_terminal(self, gap_timeout: Optional[float] = None) -> bool:
        """Park until the stream has ended: the terminal event is in (take
        it with ``next_event``, after whatever is still queued) or the
        queue was abandoned.  Incremental publishes do not wake the
        caller.  False if ``gap_timeout`` passed with no publish at all:
        a stalled generation."""
        with self._ready:
            seen = self._published
            while self._terminal is None and not self._abandoned:
                woken = self._ready.wait(gap_timeout)
                self._wakeups += 1
                if not woken:
                    if self._published == seen:
                        return False
                    seen = self._published
            return True

    def iter_events(self, timeout: Optional[float] = None
                    ) -> Iterator[StreamEvent]:
        """Yield events until (and including) the terminal one.  A
        ``timeout`` bounds each *gap* between events, not the total."""
        while True:
            ev = self.next_event(timeout=timeout)
            if ev is None:
                return
            yield ev
            if ev.terminal:
                return

    def abandon(self) -> None:
        """Consumer walked away (client disconnect): future publishes
        are shed immediately instead of filling a queue nobody reads."""
        with self._ready:
            self._abandoned = True
            self._events.clear()
            self._ready.notify_all()

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    @property
    def wakeups(self) -> int:
        """How often a consumer thread came back from a blocking wait on
        this queue (``next_event``, ``wait_terminal``)."""
        with self._lock:
            return self._wakeups
