"""Operations and bytes of what a sparse-expert, latent-attention model adds
(``lib/flops.py`` knows dense blocks with K/V heads only).  A *model* is the
dict of a configuration file's top-level keys, the published names:
``hidden_size``, ``moe_intermediate_size``, ``n_routed_experts``,
``num_experts_per_tok``, ``kv_lora_rank``, ``qk_rope_head_dim``,
``num_hidden_layers``.

What is counted:

* the grouped expert GEMMs (gate, up, down of the ROUTED experts; the shared
  expert and the router are dense GEMMs under other scopes): the weights of
  every expert that received at least one row, read once, plus each
  assignment's row in and row out; 2 FLOPs a multiply-add;
* the latent cache: one row of ``kv_lora_rank + qk_rope_head_dim`` values a
  token and layer, which is key and value at once, so it is read once.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2  # bytes


def expert_params(model: Dict) -> int:
    """Weights of one routed expert (gate, up, down)."""
    return 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def expert_gemm_cost(model: Dict, assignments: float,
                     experts_touched: float) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs need for ``assignments`` rows
    (tokens x experts per token, summed over layers) that reached
    ``experts_touched`` distinct (layer, expert) pairs."""
    h = int(model["hidden_size"])
    return {
        "flops": 2.0 * assignments * expert_params(model),
        "bytes": (experts_touched * expert_params(model)
                  + 2.0 * assignments * h) * BF16,
    }


def latent_bytes_per_token(model: Dict) -> int:
    """Bytes one cached token holds that a query must read, all layers (the
    values of the latent row; the pool's padding lanes are not needed)."""
    return (int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) \
        * BF16 * int(model["num_hidden_layers"])
