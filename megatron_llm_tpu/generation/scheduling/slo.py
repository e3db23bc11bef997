"""SLO policy — earliest-deadline-first with unmeetable-deadline shedding.

Requests carry soft deadlines: ``ttft_deadline_ms`` (first token within
this many ms of submit) and ``tpot_deadline_ms`` (per-token cadence after
the first).  Scheduling is EDF on each request's NEXT obligation:

* queued / prefilling — the absolute TTFT deadline (``inf`` when unset,
  so best-effort traffic runs after all deadlined traffic, FCFS among
  itself);
* decoding (victim ranking only) — the next token's cadence deadline
  ``t_first + tpot * (steps + 1)`` when a per-token deadline is set, else
  ``inf`` (a best-effort decoder is always the first preemption victim).

Shedding answers a request whose deadline cannot be met *now* instead of
spending pool pages on a guaranteed miss: a queued request is dropped when
its TTFT deadline has already passed, or when the predicted queue wait —
EDF position x the engine's retirement EMA — overshoots it.  Shed
requests fail with :class:`RequestShed` (HTTP 503 + Retry-After), which a
client should treat as load feedback, not an error in its request.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

from megatron_llm_tpu.generation.scheduling.policy import (
    SchedulerPolicy,
    SchedulerState,
    register_policy,
)

__all__ = ["SloPolicy", "next_obligation_deadline", "ttft_deadline"]


def ttft_deadline(req) -> float:
    """Absolute first-token deadline (monotonic seconds; inf if unset)."""
    if req.ttft_deadline_ms is None:
        return math.inf
    return req._t_submit + req.ttft_deadline_ms / 1e3


def next_obligation_deadline(req) -> float:
    """The deadline of the request's next token: TTFT until the first
    token lands, then the per-token cadence.  A decoding request with a
    TTFT deadline but no cadence deadline keeps its TTFT deadline as its
    value — NOT ``inf`` — so a freshly queued request from the same burst
    (necessarily a later deadline) cannot preempt it; only genuinely
    best-effort decoders rank as ``inf`` (first victims)."""
    if req._t_first == 0.0:
        return ttft_deadline(req)
    if req.tpot_deadline_ms is not None:
        return req._t_first + (req._step + 1) * req.tpot_deadline_ms / 1e3
    return ttft_deadline(req)


@register_policy
class SloPolicy(SchedulerPolicy):
    name = "slo"
    barrier_admission = False

    def _order(self, reqs: Sequence) -> List:
        return sorted(reqs, key=lambda r: (ttft_deadline(r), r._seqno))

    def admission_order(self, queued: Sequence,
                        state: SchedulerState) -> List:
        return self._order(queued)

    def prefill_order(self, prefilling: Sequence,
                      state: SchedulerState) -> List:
        return self._order(prefilling)

    def shed(self, queued: Sequence, state: SchedulerState
             ) -> List[Tuple[object, str]]:
        out = []
        for pos, req in enumerate(self._order(queued)):
            dl = ttft_deadline(req)
            if dl is math.inf:
                continue  # best-effort requests never shed on deadline
            if state.now > dl:
                out.append((req, "ttft deadline already passed"))
                continue
            eta = state.drain_eta(pos)
            if eta is not None and state.now + eta > dl:
                out.append((req, "predicted queue wait exceeds ttft "
                                 "deadline"))
        return out

    def preempt_victim(self, candidate, decoding: Sequence,
                       state: SchedulerState) -> Optional[object]:
        if not self.preemption:
            return None
        cand_dl = ttft_deadline(candidate)
        if cand_dl is math.inf:
            return None  # best-effort work never preempts anyone
        victims = [r for r in decoding
                   if next_obligation_deadline(r) > cand_dl]
        if not victims:
            return None
        # latest obligation (inf = best-effort) loses; among equals the
        # least-progressed resume is cheapest
        return max(victims, key=lambda r: (next_obligation_deadline(r),
                                           -len(r.generated)))
