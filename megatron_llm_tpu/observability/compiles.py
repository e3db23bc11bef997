"""The program's own count of XLA compilations.

A recompile on a shape nobody warmed up (a new prefill bucket, a changed
batch) is the classic serving stall, and a trainer that compiles twice
has a bug.  ``install_compile_counter()`` listens to jax's monitoring
event for a backend compile (a persistent-cache load fires it too) and
feeds two counters on ``GET /metrics``:

* ``mlt_jit_compiles_total``         programs compiled or loaded;
* ``mlt_jit_compile_seconds_total``  seconds spent doing so.

Called once by the entry points (``training.pretrain``, the generation
server), never at import.  The listener runs on the compiling thread,
touches no device and looks the counters up when an event fires: a
compile is rare, and a test that clears the registry keeps working.
"""

from __future__ import annotations

import threading

from megatron_llm_tpu.observability.registry import get_registry, publishing

__all__ = ["COMPILE_EVENT", "install_compile_counter"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_installed = False  # guarded by _install_lock
_install_lock = threading.Lock()


def _on_event(event: str, duration: float, **_kw) -> None:
    if event != COMPILE_EVENT or not publishing():
        return
    reg = get_registry()
    reg.counter("mlt_jit_compiles_total",
                help="XLA programs compiled (or loaded from the "
                     "persistent cache) by this process").inc()
    reg.counter("mlt_jit_compile_seconds_total",
                help="seconds spent in those compilations").inc(
        max(float(duration), 0.0))


def install_compile_counter() -> None:
    """Register the listener, once per process (jax keeps listeners for
    the life of the process, so a second call would count double)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _installed = True
