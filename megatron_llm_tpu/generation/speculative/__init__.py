"""Speculative decoding — draft-model propose, target-model verify.

At low batch the engine is latency-bound on one target forward per token.
Speculative decoding breaks that bound: a small *draft* model proposes
``k`` tokens autoregressively (cheap forwards), then the target model
scores all ``k+1`` positions in ONE forward and a lossless acceptance
rule keeps the longest prefix the target agrees with — one target-model
dispatch now yields between 1 and ``k+1`` tokens.

The subsystem lives in three pieces:

* :mod:`~megatron_llm_tpu.generation.speculative.draft` — the draft model
  bundle: a separate (same-family, smaller) config + params that share
  the target's tokenizer/vocab, resolved from ``--spec_draft`` and
  sharded by the same tp.py rules when a mesh is present.
* :mod:`~megatron_llm_tpu.generation.speculative.verify` — the lossless
  acceptance rule (greedy: bitwise-identical to non-speculative decode;
  sampled: residual rejection sampling whose output distribution provably
  equals the target model's) and the disjoint key-stream discipline.  The
  fused draft-k-then-verify tick program itself lives in
  :mod:`~megatron_llm_tpu.generation.ragged` (ISSUE 11): verify blocks
  are ordinary span-(k+1) entries of the engine's single-launch ragged
  tick, not a special-cased program.
* the engine integration (generation/engine.py): draft K/V lives in the
  SAME :class:`~megatron_llm_tpu.generation.pools.PagedKVPool` — every
  page id indexes both the target and the draft pools, so one block
  table, one refcount, one commitment ledger and one prefix trie govern
  both models' cache, and preempting a speculating slot releases draft
  pages through exactly the same trie-park path as target pages.

See docs/guide/serving.md ("Speculative decoding") for the flag table,
acceptance semantics and the losslessness contract.
"""

from megatron_llm_tpu.generation.speculative.draft import (
    DraftModel,
    check_draft_compat,
    extend_params_identity,
    resolve_draft,
)
from megatron_llm_tpu.generation.speculative.verify import (
    speculative_acceptance,
)

__all__ = [
    "DraftModel",
    "check_draft_compat",
    "extend_params_identity",
    "resolve_draft",
    "speculative_acceptance",
]
