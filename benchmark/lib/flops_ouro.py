"""Operations and bytes of what a LOOPED stack adds (Ouro: a dense stack run
``total_ut_steps`` times over the same weights, every pass with keys and
values of its own): ``lib/flops.py`` and its siblings know the other
stacks.  A *model* is the dict of a configuration file's top-level keys
beside its ``derived`` ones: ``num_hidden_layers``, ``total_ut_steps``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``hidden_size``, ``intermediate_size``.  Written from those keys alone, so
that it reads the same work whatever implements it.

What is counted:

* the layer projections a tick STREAMS: a layer's four matrices (fused QKV,
  the attention output, gate and up, down) in bf16, once a layer and PASS,
  whatever the tick's rows: up to 80 rows against 51 M weights a layer is
  far under the ~240 rows at which a bf16 GEMM leaves the bandwidth roof, so
  the weights' bytes over the HBM peak is the bound.  The norms' scales, the
  gate, the embedding and the head are not projections of a layer and are
  left out;
* the K/V a tick's rows NEED: a cached token holds ``2 x
  num_key_value_heads x head_dim`` bf16 values a layer and pass, and a
  query of pass t at layer l reads slot (t, l) of every key it sees: for a
  token received at context c that is ``c x kv_bytes_per_token``, each
  DISTINCT page once (this mix shares nothing between sequences, so a
  decode row's pages are its own; a prompt chunk's rows share one walk of
  their sequence's prefix, counted once a chunk).
"""

from __future__ import annotations

from typing import Dict

from benchmark.lib import flops_sdar

BF16 = 2  # bytes


def passes(model: Dict) -> int:
    return int(model["total_ut_steps"])


def layer_gemm_params(model: Dict) -> int:
    """Weights of one layer's projections: QKV, output, gate and up, down."""
    h, d = int(model["hidden_size"]), int(model["head_dim"])
    n, nkv = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    ffn = int(model["intermediate_size"])
    return h * (n + 2 * nkv) * d + n * d * h + h * 2 * ffn + ffn * h


def tick_gemm_bytes(model: Dict) -> int:
    """Bytes of layer projections ONE tick streams: every layer's, once a
    pass."""
    return passes(model) * int(model["num_hidden_layers"]) \
        * layer_gemm_params(model) * BF16


def cache_layer_slots(model: Dict) -> int:
    return passes(model) * int(model["num_hidden_layers"])


def kv_bytes_per_token(model: Dict) -> int:
    """Bytes of K and V one cached token holds, over all layers and passes."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) \
        * BF16 * cache_layer_slots(model)


# cached tokens the span's rows had to read, distinct pages once: a token
# received at context c read c keys; a prompt's keys so far once a chunk, by
# the share of its prefill that fell in the span.  The same sum as a block
# model's step count at one token a step
needed_keys = flops_sdar.needed_keys
