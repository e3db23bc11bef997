"""FCFS policy — the pre-policy engine's behavior, verbatim.

Admission tries exactly the queue head and blocks behind it under page
pressure (``barrier_admission``), prefill feeds the oldest prefilling
request one chunk per tick, nothing is ever preempted or shed.  This is
the default policy and MUST stay bitwise-equivalent to the inlined
scheduler it replaced: tests/test_scheduler.py locks tokens and log-probs
against the dense single-stream reference, and the PR 5 parity suites
(tests/test_prefix_cache.py) run through it unchanged.
"""

from __future__ import annotations

from megatron_llm_tpu.generation.scheduling.policy import (
    SchedulerPolicy,
    register_policy,
)

__all__ = ["FcfsPolicy"]


@register_policy
class FcfsPolicy(SchedulerPolicy):
    name = "fcfs"
    barrier_admission = True  # head waits; nothing skips it
