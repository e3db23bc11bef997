"""StreamWriter: ONE thread writes the incremental frames of every open
stream (replica tier).

The engine's apply puts a tick's tokens into the requests' queues and
the engine kicks this writer once for them (``kick``: an ``Event.set``,
outside the engine's lock, nothing the scheduler thread could block on;
it comes when the scheduler starts to wait for the device, so a pass
runs while that thread does not want the interpreter).  A pass takes
from every attached queue what has gathered there, builds one ``token``
frame a stream and hands it to that stream's socket with a send that
never blocks.  So a tick's tokens cost the interpreter one woken thread,
not one a stream.

What stays with a stream's handler thread (``generation/server.py``
``stream_response``): headers and the first frame before ``attach``, the
terminal frames after ``detach``.  Between the two the socket is this
writer's alone.

A socket that takes no (or not all) bytes keeps the rest of its frame
here (``pending``) and its later events in its own bounded
``StreamQueue``: they go out at a later pass, several events in one
frame, and past the queue's bound the queue's rule applies (incremental
events shed and counted, the terminal always delivered).  A broken
connection abandons its queue, which also wakes its handler.

Lock order: ``_Stream._lock -> StreamQueue._lock``; ``StreamWriter._lock``
guards the table of streams alone.  A stream's lock is held for that
stream's one send, so ``detach`` waits out at most that (not a pass,
which lasts as long as the scheduler leaves it the interpreter), and a
returned ``detach`` means no byte of the stream is in the writer's hands
any more.
"""

from __future__ import annotations

import socket
import threading
from typing import Callable, List, Optional, Set, Tuple

from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.observability import trace as obs_trace
from megatron_llm_tpu.serving.streaming.events import token_frame
from megatron_llm_tpu.serving.streaming.queue import StreamQueue

__all__ = ["StreamWriter"]


class _Stream:
    """One attached connection: its queue, its socket, and the unsent
    rest of a frame the socket did not take whole."""

    __slots__ = ("queue", "sock", "_lock", "_pending", "_detached")

    def __init__(self, queue: StreamQueue, sock: socket.socket):
        self.queue = queue
        self.sock = sock
        self._lock = threading.Lock()
        self._pending = b""  # guarded by _lock
        self._detached = False  # guarded by _lock

    def write(self, detokenize) -> Tuple[int, int]:
        """Send what this stream has to send, without blocking: the rest
        of its last frame, then one frame of everything its queue holds.
        Returns (frames built, sends the socket did not take whole).
        ``OSError``: the connection is gone (and the stream detached)."""
        with self._lock:
            if self._detached:
                return 0, 0
            try:
                if self._pending and not self._send_locked(self._pending):
                    return 0, 1  # still full: its events stay queued
                events = self.queue.take_tokens()
                if not events:
                    return 0, 0
                whole = self._send_locked(token_frame(events, detokenize))
                return 1, int(not whole)
            except OSError:
                self._detached, self._pending = True, b""
                raise

    def _send_locked(self, data: bytes) -> bool:  # holds _lock
        """One send that cannot block; True if the socket took it all."""
        try:
            sent = self.sock.send(data, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            sent = 0
        self._pending = data[sent:]
        return not self._pending

    def take_back(self) -> bytes:
        """End the writer's use of the socket; the unsent rest of the
        last frame."""
        with self._lock:
            self._detached = True
            return self._pending


class StreamWriter:
    """The one thread that writes every open stream's incremental frames."""

    def __init__(self,
                 detokenize: Optional[Callable[[List[int]], str]] = None):
        self._detokenize = detokenize
        self._lock = threading.Lock()
        self._streams: Set[_Stream] = set()  # guarded by _lock
        self._kick = threading.Event()
        self._stopping = False
        self._thread: Optional[threading.Thread] = None
        reg = obs_registry.get_registry()
        self._m_frames = reg.counter(
            "mlt_server_stream_frames_total",
            help="incremental token frames the stream writer built and "
                 "sent")
        self._m_passes = reg.counter(
            "mlt_server_stream_writer_passes_total",
            help="stream writer passes that sent at least one frame "
                 "(frames / passes: streams written a kick)")
        self._m_deferred = reg.counter(
            "mlt_server_stream_deferred_sends_total",
            help="stream writer sends a full socket buffer took no or "
                 "not all bytes of (the rest waits for a later pass)")

    # ---- lifecycle (MegatronServer) -------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stopping = False
        self._thread = threading.Thread(target=self._run,
                                        name="stream-writer", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stopping = True
        self._kick.set()
        self._thread.join(timeout=30)
        self._thread = None

    # ---- engine side ----------------------------------------------------

    def kick(self) -> None:
        """Queues have new events (the engine, once an applied tick);
        never blocks."""
        self._kick.set()

    # ---- handler side ---------------------------------------------------

    def attach(self, queue: StreamQueue, sock: socket.socket) -> _Stream:
        """Hand a stream's socket over: from here to ``detach`` only the
        writer sends on it.  What the queue already holds goes out with
        the engine's next kick (a kick of its own would start a pass over
        every stream beside the scheduler's plan: a request begins most
        ticks)."""
        stream = _Stream(queue, sock)
        with self._lock:
            self._streams.add(stream)
        return stream

    def detach(self, stream: _Stream) -> bytes:
        """Take a stream's socket back.  Returns the unsent rest of its
        last frame, which the caller sends first; its queue holds
        everything after that."""
        rest = stream.take_back()
        with self._lock:
            self._streams.discard(stream)
        return rest

    # ---- the writer thread ----------------------------------------------

    def _run(self) -> None:
        while True:
            self._kick.wait()
            self._kick.clear()
            if self._stopping:
                return
            self._pass()

    def _pass(self) -> None:
        frames = deferred = 0
        with obs_trace.span("serve-write"):
            with self._lock:
                streams = list(self._streams)
            for stream in streams:
                try:
                    built, late = stream.write(self._detokenize)
                except OSError:
                    # client went away mid-stream: shed its future
                    # publishes; abandon() wakes its parked handler
                    stream.queue.abandon()
                    with self._lock:
                        self._streams.discard(stream)
                    continue
                frames += built
                deferred += late
        if obs_registry.publishing():
            if frames:
                self._m_frames.inc(frames)
                self._m_passes.inc()
            if deferred:
                self._m_deferred.inc(deferred)
