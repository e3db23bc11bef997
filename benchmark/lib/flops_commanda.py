"""Operations and bytes of what a patterned stack with a held share of its
experts adds (``lib/flops.py`` knows one mask for every layer,
``lib/flops_moe.py`` a layer whose experts are all held).  A *model* is the
dict of a configuration file's top-level keys beside its ``derived`` ones:
``hidden_size``, ``intermediate_size`` (ONE expert's width), ``num_hidden_layers``,
``layer_types``, ``sliding_window``, ``num_key_value_heads``, ``head_dim``.

What is counted:

* the paged attention kernel's K/V bytes under each layer's OWN mask: a
  query at the end of ``context`` cached tokens reads ``min(context,
  sliding_window)`` keys in a ``sliding_attention`` layer and ``context`` in a
  ``full_attention`` layer, a key and its value ``2 x num_key_value_heads x
  head_dim`` bf16 values a layer; a prefill chunk needs its keys once;
* the grouped expert GEMMs of the HELD experts: the weights of every held
  expert that received at least one row, read once a tick and layer, plus
  each held assignment's row in and row out; 2 FLOPs a multiply-add.  What
  the router gave the absent experts costs this chip nothing.
"""

from __future__ import annotations

from typing import Dict, List, Optional

BF16 = 2  # bytes


def layer_windows(model: Dict) -> List[Optional[int]]:
    """The keys a query may see, a layer of the stack as it is run."""
    types = model["layer_types"]
    return [int(model["sliding_window"])
            if types[i % len(types)] == "sliding_attention" else None
            for i in range(int(model["num_hidden_layers"]))]


def key_bytes(model: Dict) -> int:
    """A cached token's key and value in ONE layer."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * BF16


def visible_key_bytes(model: Dict, context: int) -> Dict[str, float]:
    """K/V bytes one query at the end of ``context`` cached tokens must read,
    summed over the window layers and over the full layers."""
    out = {"window": 0.0, "full": 0.0}
    for w in layer_windows(model):
        out["full" if w is None else "window"] += (
            context if w is None else min(context, w)) * key_bytes(model)
    return out


def expert_params(model: Dict) -> int:
    """Weights of one routed expert (gate, up, down)."""
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def held_gemm_cost(model: Dict, held_assignments: float,
                   held_touched: float) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs need for ``held_assignments``
    rows (summed over layers) on ``held_touched`` distinct (layer, held
    expert) pairs."""
    h = int(model["hidden_size"])
    return {
        "flops": 2.0 * held_assignments * expert_params(model),
        "bytes": (held_touched * expert_params(model)
                  + 2.0 * held_assignments * h) * BF16,
    }
