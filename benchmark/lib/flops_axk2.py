"""Operations and bytes of learned sparse attention over a latent cache
(A.X-K2's indexer; the program's ``ops/sparse_attention.py``), for ONE query
row in ONE attention layer at a context of ``c`` keys; the readers multiply
by rows and layers.  A *model* is the dict of a configuration file's
top-level keys beside its ``derived`` ones: ``index_n_heads``,
``index_head_dim``, ``index_topk``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_rope_head_dim``, ``num_hidden_layers`` (every layer
of the stack as it is run is a latent-attention layer under an indexer).

What is counted is what the mechanism NEEDS, not what the program moves:

* the sweep (scope ``index_score``): every index key of the context read
  once (``index_head_dim`` bf16 values: the row's own keys, through its
  own block table), ``heads x dim`` multiply-adds a key, then the ReLU and
  the weighted sum over heads (two operations a head and key);
* the selection (scope ``index_select``): the context's float32 scores
  read once, a compare a score; the picked positions written once;
* the gather (scope ``sparse_gather``): the picked latent rows read once,
  ``kv_lora_rank + qk_rope_head_dim`` bf16 values each (the pool's padding
  lanes are not needed), nothing multiplied;
* the attention over the list (scope ``sparse_attention``): per head and
  picked row a score over the latent width and a value sum over
  ``kv_lora_rank``; the gathered rows are its only traffic, counted at the
  gather.

A row of at most ``index_topk`` keys attends all of them: its gather and
attention are counted at its context, its sweep and selection too (the
program runs them; they decide nothing).
"""

from __future__ import annotations

from typing import Dict, Iterable

BF16, F32, I32 = 2, 4, 4  # bytes


def layers(model: Dict) -> int:
    return int(model["num_hidden_layers"])


def picked(model: Dict, context: float) -> float:
    return min(float(context), float(model["index_topk"]))


def sweep_cost(model: Dict, context: float) -> Dict[str, float]:
    h, d = int(model["index_n_heads"]), int(model["index_head_dim"])
    return {"flops": context * (2.0 * h * d + 2.0 * h),
            "bytes": context * d * BF16 + h * d * BF16 + context * F32}


def select_cost(model: Dict, context: float) -> Dict[str, float]:
    return {"flops": float(context),
            "bytes": context * F32 + picked(model, context) * I32}


def latent_width(model: Dict) -> int:
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def gather_cost(model: Dict, context: float) -> Dict[str, float]:
    return {"flops": 0.0,
            "bytes": picked(model, context) * latent_width(model) * BF16}


def attention_cost(model: Dict, context: float) -> Dict[str, float]:
    n, r = int(model["num_attention_heads"]), int(model["kv_lora_rank"])
    return {"flops": picked(model, context) * n * 2.0 * (latent_width(model) + r),
            "bytes": n * (latent_width(model) + r) * BF16}


def total(cost, model: Dict, rows: Iterable) -> Dict[str, float]:
    """``cost`` summed over ``rows`` ((context, how many rows, how many
    READERS) triples), times the layers.  Operations are a row's; bytes are
    a reader's: a decode row reads its own context, and the rows of ONE
    prompt chunk read the same keys, which are needed once a chunk however
    many rows the chunk has (as ``readers.paged_roofline`` counts a
    chunk's keys once), and of which they cannot need more latent rows
    than the context holds."""
    out = {"flops": 0.0, "bytes": 0.0}
    for context, weight, readers in rows:
        one = cost(model, context)
        out["flops"] += weight * one["flops"]
        share = min(weight * picked(model, context), readers * context) / (
            weight * picked(model, context)) if cost in (
                gather_cost, attention_cost) else readers / weight
        out["bytes"] += weight * one["bytes"] * share
    return {k: v * layers(model) for k, v in out.items()}


def span_rows(run) -> list:
    """(context, rows, readers) of what the traced span ran, from the
    client's samples.  A token a client received in the span was ONE decode
    row at the context that made it, its own reader.  A prompt prefilled in
    the span is a row a token past what the prefix cache served (the
    samples do not say what that was: the mix's primed prefix less its last
    page, where the request carries one), in chunks of the engine's
    ``prefill_chunk`` rows that read their keys once each, by the share of
    its prefill that fell in the span, entered at the rows' MEAN context
    (every cost here is linear in the context past ``index_topk``)."""
    a, b = run.trace_host
    shared = run.cell.traffic.get("shared_prefix") or {}
    page = int(run.engine.get("page_size") or 16)
    chunk = int(run.engine.get("prefill_chunk") or 64)
    out = []
    for s in run.all_samples:
        n_prompt = s["n_prompt"]
        out += [(float(n_prompt + i), 1.0, 1.0)
                for i, ts in enumerate(s["token_t"]) if a <= ts <= b]
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is None or first is None or first <= sent:
            continue
        overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
        cached = 0
        if s.get("prefix") is not None and shared.get("prime"):
            cached = (min(int(shared["tokens"]), n_prompt) - 1) // page * page
        rows = max(0, n_prompt - 1 - cached)        # the last is decoded
        if overlap > 0 and rows:
            out.append((cached + (rows + 1) / 2.0, overlap * rows,
                        overlap * -(-rows // chunk)))
    return out
