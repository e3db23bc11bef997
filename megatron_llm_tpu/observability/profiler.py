"""On-demand ``jax.profiler`` window capture — the real ``utils/profiler``
the timers.py docstring promised since the seed.

The static ``--profile`` window (training.py) answers "what does step 11
look like"; this module answers the operational question "what does the
job look like RIGHT NOW" without restarting it.  Two triggers arm a
capture:

* ``kill -USR2 <pid>``                    (install_sigusr2)
* ``GET /profile?steps=N`` on the metrics endpoint (exporter.py)

Both only set a flag — the actual ``start_trace``/``stop_trace`` happen
on the driver thread at step boundaries (``maybe_start``/``step_done``),
because the profiler must bracket whole dispatched steps and must never
run from a signal-handler frame.  Output is bounded: at most
``max_captures`` windows per process, each in its own subdirectory of
``out_dir`` (xplane format — open with xprof / tensorboard-profile).
"""

from __future__ import annotations

import os
import signal
import threading
from typing import Callable, Dict, List, Optional

__all__ = ["ProfileTrigger", "install_sigusr2", "profile_dir"]


def profile_dir(logging_cfg) -> str:
    """Where a process's profiles go: ``--profile_dir``, else ``profile``
    under ``--tensorboard_dir`` (or the working directory).  On-demand
    windows land in its ``ondemand`` subdirectory."""
    return logging_cfg.profile_dir or os.path.join(
        logging_cfg.tensorboard_dir or ".", "profile")


def _jax_start(logdir: str) -> None:
    import jax

    jax.profiler.start_trace(logdir)


def _jax_stop() -> None:
    import jax

    jax.profiler.stop_trace()


class ProfileTrigger:
    """Arm-from-anywhere, capture-on-the-driver profiling window.

    Thread-safe: ``request`` may be called from HTTP handler threads or a
    signal handler; ``maybe_start``/``step_done``/``close`` belong to the
    driver thread (the one dispatching steps).

    Args:
      out_dir: parent directory for capture subdirs (created lazily).
      default_steps: window length when a request names none.
      max_captures: process-lifetime budget — the output dir stays bounded
        no matter how often someone curls ``/profile``.
      start_fn / stop_fn: injection points for tests; default to
        ``jax.profiler.start_trace`` / ``stop_trace``.
    """

    def __init__(self, out_dir: str, default_steps: int = 2,
                 max_captures: int = 8,
                 start_fn: Callable[[str], None] = _jax_start,
                 stop_fn: Callable[[], None] = _jax_stop):
        self.out_dir = out_dir
        self.default_steps = max(int(default_steps), 1)
        self.max_captures = max(int(max_captures), 1)
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._lock = threading.Lock()
        # steps wanted, not started — guarded by _lock
        self._requested: Optional[int] = None
        # steps left in live capture — guarded by _lock
        self._remaining: Optional[int] = None
        self.captures = 0
        self.capture_dirs: List[str] = []

    # ---- trigger side (any thread) ----

    def request(self, steps: Optional[int] = None) -> Dict:
        """Arm a capture of ``steps`` steps; returns a status dict (the
        /profile response body)."""
        steps = self.default_steps if steps is None else int(steps)
        if steps < 1:
            return {"accepted": False, "error": "steps must be >= 1"}
        with self._lock:
            if self._requested is not None or self._remaining is not None:
                return {"accepted": False,
                        "error": "a capture is already pending or active"}
            if self.captures >= self.max_captures:
                return {"accepted": False,
                        "error": f"capture budget exhausted "
                                 f"(max_captures={self.max_captures})"}
            self._requested = steps
            return {"accepted": True, "steps": steps,
                    "capture_index": self.captures,
                    "out_dir": self.out_dir}

    @property
    def active(self) -> bool:
        with self._lock:
            return self._remaining is not None

    @property
    def pending(self) -> bool:
        with self._lock:
            return self._requested is not None

    # ---- driver side (step boundaries) ----

    def maybe_start(self, iteration: int) -> Optional[str]:
        """Start a requested capture before dispatching ``iteration``.
        Returns the capture dir when one starts, else None."""
        with self._lock:
            if self._requested is None or self._remaining is not None:
                return None
            steps = self._requested
            self._requested = None
            logdir = os.path.join(
                self.out_dir,
                f"ondemand_{self.captures:03d}_iter{iteration:08d}")
            self.captures += 1
            self.capture_dirs.append(logdir)
            self._remaining = steps
        os.makedirs(logdir, exist_ok=True)
        self._start_fn(logdir)
        return logdir

    def step_done(self) -> bool:
        """Count one finished step against a live window; stops the
        capture when the window completes.  Returns True on stop."""
        with self._lock:
            if self._remaining is None:
                return False
            self._remaining -= 1
            if self._remaining > 0:
                return False
            self._remaining = None
        self._stop_fn()
        return True

    def close(self) -> None:
        """Stop a live capture (early driver exit must not leak one)."""
        with self._lock:
            live, self._remaining = self._remaining is not None, None
            self._requested = None
        if live:
            self._stop_fn()


def install_sigusr2(trigger: ProfileTrigger,
                    steps: Optional[int] = None):
    """Route ``SIGUSR2`` to ``trigger.request``; returns the previous
    handler (restore it when the loop exits), or None when signals cannot
    be installed here (only the main thread may set handlers — tests and
    library embedders call ``pretrain`` from worker threads)."""
    if threading.current_thread() is not threading.main_thread():
        return None

    def _handler(signum, frame):
        trigger.request(steps)  # flag only; capture starts on the driver

    try:
        return signal.signal(signal.SIGUSR2, _handler)
    except (ValueError, OSError, AttributeError):
        return None
