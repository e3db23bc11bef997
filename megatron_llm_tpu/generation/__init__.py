"""Text generation — megatron/text_generation analog, plus the
continuous-batching serving engine (generation/engine.py) over its pools
(generation/pools.py)."""

from megatron_llm_tpu.generation.api import InferenceEngine
from megatron_llm_tpu.generation.engine import (
    ContinuousBatchingEngine,
    EngineOverloaded,
    EngineRequest,
)
from megatron_llm_tpu.generation.generation import (
    beam_search,
    generate_tokens,
    score_tokens,
)
from megatron_llm_tpu.generation.pools import (
    PagedKVPool,
    PrefixCache,
    StatePool,
)
from megatron_llm_tpu.generation.sampling import sample, sample_per_slot
from megatron_llm_tpu.generation.scheduling import (
    RequestShed,
    SchedulerPolicy,
    get_policy,
)
from megatron_llm_tpu.generation.speculative import DraftModel, resolve_draft

__all__ = [
    "ContinuousBatchingEngine",
    "DraftModel",
    "EngineOverloaded",
    "EngineRequest",
    "InferenceEngine",
    "PagedKVPool",
    "PrefixCache",
    "RequestShed",
    "SchedulerPolicy",
    "StatePool",
    "beam_search",
    "generate_tokens",
    "get_policy",
    "resolve_draft",
    "sample",
    "sample_per_slot",
    "score_tokens",
]
