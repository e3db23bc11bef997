"""Operations and bytes of a stack that mixes kinds of layer and holds a
share of its experts (``lib/flops.py`` knows one window for every layer and
no experts; ``lib/flops_moe.py`` a serving tick's weight streaming).  A
*model* is the dict of a configuration file's keys, the published names:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_ffn_hidden_size``, ``moe_num_primary_experts`` (the
experts HELD here), ``moe_num_active_primary_experts``, ``router_width``
(the published expert count), ``sliding_window_size``,
``sliding_window_layout`` and ``rope_layout`` (ONE period),
``num_hidden_layers``, ``vocab_size``.

What is counted, as in ``lib/flops.py``: matmul work only, 2 FLOPs a
multiply-add; QK^T and AV over the keys a causal query may see under ITS
layer's mask; backward = 2 x forward; no recomputation, no gather, no norm,
no softmax.  Experts: the rows the held experts run, nothing for the absent
ones (their part of the layer is computed on other chips).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.lib import flops

BF16 = 2  # bytes


def layer_windows(model: Dict) -> List[Optional[int]]:
    """The window of every layer of the stack (None = full causal)."""
    layout = model["sliding_window_layout"]
    return [int(model["sliding_window_size"]) if layout[i % len(layout)]
            else None for i in range(int(model["num_hidden_layers"]))]


def _attention_view(model: Dict, window: Optional[int]) -> Dict:
    """One kind of layer as ``lib/flops.py`` reads a dense model."""
    return {**model, "intermediate_size": 0, "sliding_window": window}


def attention_flops_fwd(model: Dict, seq: int) -> float:
    """QK^T and AV for one sequence through EVERY layer, forward."""
    return sum(flops.attention_flops_fwd(_attention_view(model, w), seq)
               for w in layer_windows(model))


def expert_params(model: Dict) -> int:
    """Weights of one expert (gate, up, down)."""
    return 3 * int(model["hidden_size"]) * int(model["moe_ffn_hidden_size"])


def held_share(model: Dict) -> float:
    """The part of the router's assignments whose expert is held here, if
    the router spreads them evenly."""
    return int(model["moe_num_primary_experts"]) / int(model["router_width"])


def dense_matmul_params(model: Dict) -> int:
    """Weights every token multiplies: attention and router of each layer,
    and the head (the held slice of the vocabulary)."""
    h = int(model["hidden_size"])
    n, nkv = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    d = int(model["head_dim"])
    layer = h * (n + 2 * nkv) * d + n * d * h + h * int(model["router_width"])
    return int(model["num_hidden_layers"]) * layer + h * int(model["vocab_size"])


def train_flops_per_token(model: Dict, seq: int,
                          held_per_token: Optional[float] = None) -> float:
    """Forward + backward matmul FLOPs per trained token at ``seq`` on this
    chip.  ``held_per_token``: assignments a token and layer whose expert is
    held (default: the even share, top-k x held / router width)."""
    if held_per_token is None:
        held_per_token = (int(model["moe_num_active_primary_experts"])
                          * held_share(model))
    layers = int(model["num_hidden_layers"])
    fwd = (2.0 * dense_matmul_params(model)
           + 2.0 * layers * held_per_token * expert_params(model)
           + attention_flops_fwd(model, seq) / seq)
    return 3.0 * fwd


def flash_train_cost(model: Dict, seq: int, sequences: float) -> Dict:
    """FLOPs and bytes the flash forward + backward kernels need for
    ``sequences`` sequences through the whole stack, each layer under its
    own mask, and the part of both that the window layers need."""
    total = {"flops": 0.0, "bytes": 0.0, "window_flops": 0.0}
    for w in layer_windows(model):
        cost = flops.flash_train_cost(_attention_view(model, w), seq,
                                      sequences, layers=1)
        total["flops"] += cost["flops"]
        total["bytes"] += cost["bytes"]
        if w is not None:
            total["window_flops"] += cost["flops"]
    return total


def expert_gemm_train_cost(model: Dict, held_rows: float,
                           steps: float) -> Dict[str, float]:
    """FLOPs and bytes the grouped GEMMs (up, gate, down) need for
    ``held_rows`` assignments (summed over layers and steps) in ``steps``
    steps: forward and both gradients, three passes of 2 FLOPs a
    multiply-add; each pass reads (or writes, for the weights' gradient)
    the held experts' weights once a step and a row in and a row out."""
    h, layers = int(model["hidden_size"]), int(model["num_hidden_layers"])
    weights = steps * layers * int(model["moe_num_primary_experts"]) \
        * expert_params(model)
    return {"flops": 3 * 2.0 * held_rows * expert_params(model),
            "bytes": 3 * (weights + 2.0 * held_rows * h) * BF16}
