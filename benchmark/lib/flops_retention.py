"""Bytes of what a power-retention layer (the program's ``ops/retention.py``)
needs from memory.  A *model* is the dict of a configuration file's
top-level keys beside its ``derived`` ones: ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``.

What is counted is the MINIMAL symmetric state, whatever layout or kernel
implements it: the degree-2 feature map of a head of ``d`` values has ``d (d
+ 1) / 2`` distinct products (8,256 at 128), so a KV head's state is that
many rows of ``d`` float32 values and as many float32 normalisers.  A RUN
(a sequence's consecutive rows of one tick: one decode row, or the prompt
rows a tick packs for one request) needs its state read ONCE and written
ONCE a layer, plus each of its rows' q, k and v in float32.  A layout that
stores more (the program's tiles: 8,704) moves more than is counted here and
reads a lower share for it.
"""

from __future__ import annotations

from typing import Dict

F32 = 4  # bytes


def minimal_features(model: Dict) -> int:
    d = int(model["head_dim"])
    return d * (d + 1) // 2


def state_bytes(model: Dict) -> int:
    """One sequence's state in ONE layer: every KV head's S and z."""
    d = int(model["head_dim"])
    return int(model["num_key_value_heads"]) * (
        minimal_features(model) * d + minimal_features(model)) * F32


def row_bytes(model: Dict) -> int:
    """One row's q, k and v in one layer, float32."""
    return (int(model["num_attention_heads"])
            + 2 * int(model["num_key_value_heads"])) * int(
        model["head_dim"]) * F32


def sweep_bytes(model: Dict, runs: float, rows: float) -> float:
    """HBM bytes the state sweep needs for ``runs`` runs of ``rows`` rows
    in all, over every layer: a read and a write of each run's state, and
    the rows' q, k, v."""
    layers = int(model["num_hidden_layers"])
    return layers * (2.0 * runs * state_bytes(model)
                     + rows * row_bytes(model))
