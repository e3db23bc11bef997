"""Operations and bytes of what an SDAR stack adds (generation by diffusion
over blocks on block-causal K/V pages; SwiGLU experts over a held share):
``lib/flops_lfm2.py`` and its siblings know the other stacks.  A *model* is
the dict of a configuration file's top-level keys beside its ``derived``
ones: ``num_hidden_layers``, ``num_key_value_heads``, ``head_dim``,
``hidden_size``, ``expert_params``, ``diffusion_block_length``.  Written from
those keys alone, so that it reads the same work whatever implements it.

What is counted:

* the K/V a denoising step NEEDS: a block's ``B`` denoise rows (and the
  commit rows of the block before, where they ride in the same tick) name
  ONE sequence and ONE mask position, so a step needs that sequence's keys
  and values ONCE a layer, however many of its rows run and however often a
  kernel walks them: ``2 x num_key_value_heads x head_dim`` bf16 values a
  token and layer.  A kernel that walks a block's rows one by one reads
  under 100 / B.  Under one token a step (the cell's ``sequential``
  unmasking; random weights pass no confidence threshold) a token a client
  received is one step at its context so far; a trained model that unmasks
  several tokens in a step needs fewer steps a token, which this count
  would overstate (PERF.md section 7);
* a prompt's block-causal prefill: its keys so far once a chunk;
* the grouped expert GEMMs of the HELD experts: an expert is THREE matrices
  (``expert_params`` = 3 x hidden x moe_intermediate: SwiGLU), read once
  where it received a row, plus each assignment's row in and row out.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark.lib import flops_lfm2

BF16 = 2  # bytes


def kv_bytes_per_token(model: Dict) -> int:
    """Bytes of K and V one cached token holds, over all layers."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) \
        * BF16 * int(model["num_hidden_layers"])


def block_rows(model: Dict, blocks: float, steps: float) -> float:
    """Rows ``blocks`` committed blocks and ``steps`` denoising steps run:
    ``B`` a step and ``B`` a commit."""
    return int(model["diffusion_block_length"]) * (blocks + steps)


def needed_keys(samples: Iterable[Dict], span: Tuple[float, float],
                chunk: int) -> float:
    """Cached tokens the span's steps and prompt chunks had to read: a
    step's sequence once (one step a token received), a prompt's keys so
    far once a chunk, by the share of its prefill that fell in the span."""
    a, b = span
    keys = 0.0
    for s in samples:
        n_prompt = s["n_prompt"]
        keys += sum(n_prompt + i for i, ts in enumerate(s["token_t"])
                    if a <= ts <= b)
        sent = s.get("sent_t")
        first = s["token_t"][0] if s["token_t"] else None
        if sent is not None and first is not None and first > sent:
            overlap = max(0.0, min(b, first) - max(a, sent)) / (first - sent)
            keys += overlap * sum(min(e, n_prompt) for e in range(
                chunk, n_prompt + chunk, chunk))
    return keys


# an expert here is what it is there: THREE matrices of ``expert_params``,
# read once where it received a row, plus each assignment's row in and out
held_gemm_cost = flops_lfm2.held_gemm_cost
