"""Operations and bytes of what a hybrid stack of gated-delta (linear)
layers and latent attention over a held share of its experts adds
(``lib/flops_retention.py`` knows a state that is only added to,
``lib/flops_moe.py`` a model whose every layer keeps latent rows and whose
every expert is held).  A *model* is the dict of a configuration file's
top-level keys beside its ``derived`` ones: ``hidden_size``,
``moe_intermediate_size``, ``kv_lora_rank``, ``qk_rope_head_dim``,
``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``, ``num_hidden_layers``,
``full_attention_layers`` (of the stack as it is run).

What is counted:

* the state sweep (the program's ``delta_sweep`` kernel): a value head's
  state ``S [dk, dv]`` float32, read ONCE and written ONCE a RUN (a
  sequence's consecutive rows of one tick: one decode row, or the prompt
  rows a tick packs for one request), layer and value head, plus each
  row's q and k (a key head's, once), v, g and beta in float32.  The conv's
  tail is the convolution's, outside the kernel, and is not counted;
* the latent rows of the layers that ARE latent attention: one row of
  ``kv_lora_rank + qk_rope_head_dim`` bf16 values a token and such layer,
  key and value at once (ONE layer in five here);
* the grouped expert GEMMs of the HELD experts, as
  ``lib/flops_commanda.py`` has them, at this model's expert width.
"""

from __future__ import annotations

from typing import Dict

from benchmark.lib.flops_moe import expert_params

BF16, F32 = 2, 4  # bytes


def linear_layers(model: Dict) -> int:
    """Layers of the stack as it is run that keep a state."""
    return int(model["num_hidden_layers"]) - latent_layers(model)


def latent_layers(model: Dict) -> int:
    return len(model["full_attention_layers"])


def state_bytes(model: Dict) -> int:
    """One sequence's state in ONE linear layer: every value head's S."""
    return (int(model["linear_num_value_heads"])
            * int(model["linear_key_head_dim"])
            * int(model["linear_value_head_dim"]) * F32)


def row_bytes(model: Dict) -> int:
    """One row's q, k, v, g and beta in one linear layer, float32."""
    hk, hv = (int(model["linear_num_key_heads"]),
              int(model["linear_num_value_heads"]))
    return (2 * hk * int(model["linear_key_head_dim"])
            + hv * int(model["linear_value_head_dim"]) + 2 * hv) * F32


def sweep_bytes(model: Dict, runs: float, rows: float) -> float:
    """HBM bytes the state sweep needs for ``runs`` runs of ``rows`` rows
    in all, over every linear layer."""
    return linear_layers(model) * (2.0 * runs * state_bytes(model)
                                   + rows * row_bytes(model))


def latent_bytes_per_token(model: Dict) -> int:
    """Bytes one cached token holds that a query must read, over the
    latent layers (the pool's padding lanes are not needed)."""
    return (int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) \
        * BF16 * latent_layers(model)


def held_gemm_cost(model: Dict, held_assignments: float,
                   held_touched: float) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs need for ``held_assignments``
    rows (summed over layers) on ``held_touched`` distinct (layer, held
    expert) pairs; what the router gave the absent experts costs nothing."""
    h = int(model["hidden_size"])
    return {
        "flops": 2.0 * held_assignments * expert_params(model),
        "bytes": (held_touched * expert_params(model)
                  + 2.0 * held_assignments * h) * BF16,
    }
