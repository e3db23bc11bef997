"""Cross-replica KV page handoff (ISSUE 19).

Disaggregated prefill/decode serving splits a request across two
replicas: a prefill-role replica runs chunked prefill, exports the
prompt's full KV pages (quantized bytes + per-page scale rows + draft
KV when speculating) and pushes them to a decode-role replica, which
installs them as a :class:`~megatron_llm_tpu.generation.pools.PrefixCache`
insert — a migrated prefix is indistinguishable from a locally-cached
one, so COW / refcount / eviction invariants hold unchanged.

* :mod:`wire` — the length-prefixed wire format (:func:`encode_pages`
  / :func:`decode_pages`); byte-exact round-trip for every kv_dtype.
* :mod:`transfer` — the push client (:func:`push_pages` →
  ``POST /admin/kv_push``) and its lock-disciplined stats.

Routing lives in ``serving/router`` (the ``disagg`` policy); the
replica endpoints in ``generation/server.py``.
"""

from megatron_llm_tpu.serving.handoff.wire import (
    HandoffPayload,
    decode_pages,
    encode_pages,
)
from megatron_llm_tpu.serving.handoff.transfer import (
    STATS,
    HandoffStats,
    KVPushError,
    push_pages,
)

__all__ = [
    "HandoffPayload",
    "HandoffStats",
    "KVPushError",
    "STATS",
    "decode_pages",
    "encode_pages",
    "push_pages",
]
