"""Operations and bytes of what an LFM2 stack adds (gated short convolutions
on a per-sequence conv tail, QK-normed GQA layers on K/V pages in ten layers
of forty, SwiGLU experts over a held share): ``lib/flops_mamba.py``,
``lib/flops_delta.py`` and ``lib/flops_retention.py`` know the other state
classes, ``lib/flops.py`` a model whose EVERY layer keeps keys and values.
A *model* is the dict of a configuration file's top-level keys beside its
``derived`` ones: ``layer_types`` (a name a layer), ``hidden_size``,
``conv_L_cache``, ``num_key_value_heads``, ``head_dim``, ``expert_params``.
Written from those keys alone, so that it reads the same work whatever
implements the mixer (a kernel's own layout, padding or blocking is not
needed work).

What is counted:

* the short-conv mixer, every conv layer: both projections' weights
  (``hidden x 3 hidden`` in, ``hidden x hidden`` out, bf16) and the filter
  ONCE a tick, whatever its rows; each live row's normed input and its
  output (``hidden`` bf16 values each); a run's tail (``conv_L_cache - 1``
  rows of ``hidden`` values, in the activations' bf16) read ONCE and
  written ONCE a run (a sequence's consecutive rows of one tick: one decode
  row, or the prompt rows a tick packs for one request).  What lies between
  the two projections (B, C, u, the gated and convolved rows) stays on the
  chip in the least form and is not counted;
* the K/V rows of the layers that ARE attention: ``2 x num_key_value_heads
  x head_dim`` bf16 values a token and such layer;
* the grouped expert GEMMs of the HELD experts: an expert is THREE matrices
  (``expert_params`` = 3 x hidden x moe_intermediate: SwiGLU), read once
  where it received a row, plus each assignment's row in and row out.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2  # bytes


def layers_of(model: Dict, kind: str) -> int:
    """Layers of the stack whose mixer is ``kind`` (``conv`` or
    ``full_attention``)."""
    return list(model["layer_types"]).count(kind)


def mixer_weight_bytes(model: Dict) -> int:
    """ONE conv layer's in-projection, filter and out-projection."""
    h = int(model["hidden_size"])
    return (h * 3 * h + int(model["conv_L_cache"]) * h + h * h) * BF16


def tail_bytes(model: Dict) -> int:
    """One sequence's conv tail in ONE conv layer."""
    return (int(model["conv_L_cache"]) - 1) * int(model["hidden_size"]) * BF16


def mixer_bytes(model: Dict, ticks: float, runs: float, rows: float) -> float:
    """HBM bytes the short-conv mixers need for ``ticks`` ticks that held
    ``runs`` runs of ``rows`` rows in all, over every conv layer."""
    row = 2 * int(model["hidden_size"]) * BF16          # in and out
    return layers_of(model, "conv") * (
        ticks * mixer_weight_bytes(model) + rows * row
        + 2.0 * runs * tail_bytes(model))


def kv_bytes_per_token(model: Dict) -> int:
    """Bytes of K and V one cached token holds that a query must read, over
    the attention layers (``lib/flops.kv_bytes_per_token`` counts every
    layer of the stack)."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) \
        * BF16 * layers_of(model, "full_attention")


def held_gemm_cost(model: Dict, held_assignments: float,
                   held_touched: float) -> Dict[str, float]:
    """FLOPs and HBM bytes the grouped GEMMs need for ``held_assignments``
    rows (summed over layers) on ``held_touched`` distinct (layer, held
    expert) pairs; what the router gave the absent experts costs nothing."""
    params = int(model["expert_params"])
    return {
        "flops": 2.0 * held_assignments * params,
        "bytes": (held_touched * params
                  + 2.0 * held_assignments * int(model["hidden_size"])) * BF16,
    }
