"""Priority policy — per-request classes with an anti-starvation bound.

Requests carry an integer ``priority`` (0 = most urgent; default 1).
Ordering uses the AGED effective priority

    effective(r) = r.priority - waited_seconds / aging_s

so a request climbs one class per ``aging_s`` seconds in the queue: a
class-``p`` request is guaranteed to outrank fresh class-0 arrivals after
at most ``p * aging_s`` seconds — the starvation bound
(tests/test_scheduler.py::test_priority_starvation_bound).

Preemption compares aged values on BOTH sides: a candidate may only evict
a decoding request whose effective priority is strictly worse, so an aged
low-class request that finally admitted cannot be bounced back out by the
next fresh high-class arrival (no preemption livelock), and among eligible
victims the least-progressed one loses (cheapest resume: fewest pages to
re-match, fewest suffix tokens to re-prefill).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from megatron_llm_tpu.generation.scheduling.policy import (
    SchedulerPolicy,
    SchedulerState,
    register_policy,
)

__all__ = ["PriorityPolicy"]


@register_policy
class PriorityPolicy(SchedulerPolicy):
    name = "priority"
    barrier_admission = False  # a small request may fill around a big one

    def effective(self, req, now: float) -> float:
        """Aged priority: lower = more urgent; falls one class per
        ``aging_s`` seconds waited."""
        return req.priority - (now - req._t_submit) / self.aging_s

    def _order(self, reqs: Sequence, now: float) -> List:
        return sorted(reqs, key=lambda r: (self.effective(r, now),
                                           r._seqno))

    def admission_order(self, queued: Sequence,
                        state: SchedulerState) -> List:
        return self._order(queued, state.now)

    def prefill_order(self, prefilling: Sequence,
                      state: SchedulerState) -> List:
        # an urgent prompt's chunks jump ahead of a batch prompt's
        return self._order(prefilling, state.now)

    def preempt_victim(self, candidate, decoding: Sequence,
                       state: SchedulerState) -> Optional[object]:
        if not self.preemption:
            return None
        cand_eff = self.effective(candidate, state.now)
        victims = [r for r in decoding
                   if self.effective(r, state.now) > cand_eff + 1e-9]
        if not victims:
            return None
        # lowest value first; among those, least progress lost
        return max(victims, key=lambda r: (self.effective(r, state.now),
                                           -len(r.generated)))
