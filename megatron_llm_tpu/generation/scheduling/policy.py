"""SchedulerPolicy — the decision interface the engine delegates to.

A policy never touches engine mechanisms: it sees request objects and a
read-only :class:`SchedulerState` snapshot and answers four questions —

* **admission order**: which queued requests should admission try, in
  what order, and does a blocked best-candidate block everyone behind it
  (``barrier_admission``, the FCFS no-starvation property)?
* **prefill schedule**: which prefilling request gets the next chunk, and
  how many prompt TOKENS may prefill this tick (``prefill_budget`` —
  token-denominated, NOT a chunk count; see its docstring)?
* **preemption**: when the best queued candidate cannot admit (no slot,
  or the page budget is short), which decoding request — if any — should
  release its pages and re-queue?  The engine only calls this when
  preemption can resume bitwise (chunked-prefill mode) and the victim set
  already excludes non-preemptible requests (``return_log_probs``).
* **shedding**: which queued requests should be dropped outright (answer
  now with a retryable error) because serving them would only miss their
  deadline and waste pool pages?

Policies must be side-effect free: every method takes snapshots and
returns decisions; the engine applies them under its own lock.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Type

__all__ = [
    "RequestShed",
    "SchedulerPolicy",
    "SchedulerState",
    "available_policies",
    "get_policy",
    "register_policy",
]


class RequestShed(RuntimeError):
    """The scheduler dropped this request before serving it.

    Raised from ``EngineRequest.result()``; the server maps it to a
    structured 503 with a Retry-After hint (generation/server.py) — the
    client's signal to back off or relax its deadline."""

    def __init__(self, msg: str, retry_after: float = 1.0,
                 info: Optional[dict] = None):
        super().__init__(msg)
        self.retry_after = retry_after
        self.info = info or {}


@dataclasses.dataclass(frozen=True)
class SchedulerState:
    """Read-only engine snapshot for policy decisions (built under the
    engine lock — policies must not call back into the engine)."""

    now: float                       # time.monotonic() at decision time
    ema_tick_s: Optional[float]      # EMA decode-tick wall time
    ema_retire_s: Optional[float]    # EMA interval between retirements
    free_slots: int
    queue_depth: int
    prefill_chunk: int = 0           # engine chunk size in tokens
    # measured submit-to-first-token EMA (ISSUE 12, flight-recorder
    # derived): the REAL first-token latency of recent requests —
    # includes queue + prefill, unlike the tick/retire EMAs.  None until
    # the first token ever lands.  Policies may use it to ground their
    # wait predictions in observed TTFT rather than drain arithmetic.
    ttft_ema_s: Optional[float] = None

    def drain_eta(self, depth: int) -> Optional[float]:
        """Predicted seconds until ``depth`` queued requests drain, from
        the retirement EMA (tick EMA as a coarse floor before the first
        retirement).  None until any timing signal exists."""
        per = self.ema_retire_s if self.ema_retire_s is not None \
            else self.ema_tick_s
        if per is None:
            return None
        return depth * per


class SchedulerPolicy:
    """Base policy: FCFS-shaped defaults; subclasses override decisions.

    ``aging_s`` is the anti-starvation horizon (priority: one class per
    ``aging_s`` seconds waited); ``preemption`` gates preempt_victim for
    policies that support it."""

    name = "base"
    #: True = admission stops at the first blocked candidate (strict FCFS:
    #: nothing skips the queue head); False = admission keeps trying the
    #: rest of the order, so a small request can fill around a big one.
    barrier_admission = False

    def __init__(self, *, aging_s: float = 5.0, preemption: bool = True):
        if aging_s <= 0:
            raise ValueError("aging_s must be positive")
        self.aging_s = aging_s
        self.preemption = preemption

    # ---- admission -----------------------------------------------------

    def admission_order(self, queued: Sequence, state: SchedulerState
                        ) -> List:
        """Queued requests in the order admission should try them."""
        return list(queued)

    # ---- prefill -------------------------------------------------------

    def prefill_order(self, prefilling: Sequence, state: SchedulerState
                      ) -> List:
        """Prefilling requests; the first gets the next chunk."""
        return list(prefilling)

    def prefill_budget(self, prefilling: Sequence,
                       state: SchedulerState) -> int:
        """Prompt TOKENS the engine may prefill this tick.

        The unit is TOKENS, not chunks (ISSUE 11 pinned the ambiguity):
        the engine floors the budget to at least one chunk
        (``state.prefill_chunk``) so prefill always advances, and caps it
        at its prefill capacity (``--prefill_budget``; unset, the decode
        width in whole chunks); a budget of N tokens may therefore admit
        MULTIPLE chunks from MULTIPLE prefilling requests into one tick
        (tests/test_ragged_tick.py pins the regression).
        The default spends that capacity while prompts wait: it asks for
        the rows ``prefilling`` still needs, each prompt counted to the
        end of its last chunk (an upper bound: the engine packs the rows
        there are).  Waiting prompts hold their slots, so the slots an
        engine has fill as fast as its geometry lets them; an engine of
        at most one chunk of slots is capped at one chunk a tick, the
        pre-policy interleave.  Negative returns are a policy bug and
        raise."""
        chunk = max(state.prefill_chunk, 1)
        need = sum(
            -(-(len(r.prompt) + len(r.generated)) // chunk) * chunk
            - r._fill_pos for r in prefilling)
        return max(need, chunk)

    # ---- shedding ------------------------------------------------------

    def shed(self, queued: Sequence, state: SchedulerState
             ) -> List[Tuple[object, str]]:
        """(request, reason) pairs to drop from the queue right now."""
        return []

    # ---- preemption ----------------------------------------------------

    def preempt_victim(self, candidate, decoding: Sequence,
                       state: SchedulerState) -> Optional[object]:
        """The decoding request that should release its pages so
        ``candidate`` can admit — or None to wait instead.  Must only
        return a victim STRICTLY less valuable than the candidate, or
        admission livelocks on mutual preemption."""
        return None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_POLICIES: Dict[str, Type[SchedulerPolicy]] = {}


def register_policy(cls: Type[SchedulerPolicy]) -> Type[SchedulerPolicy]:
    """Class decorator: make ``cls`` reachable as --sched_policy <name>."""
    if not cls.name or cls.name == "base":
        raise ValueError("policy classes must set a unique `name`")
    _POLICIES[cls.name] = cls
    return cls


def get_policy(name: str) -> Type[SchedulerPolicy]:
    try:
        return _POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown scheduling policy {name!r}; available: "
            f"{', '.join(sorted(_POLICIES))}") from None


def available_policies() -> List[str]:
    return sorted(_POLICIES)
