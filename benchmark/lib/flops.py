"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  A *model* here is the dict of a configuration file's top-level
keys (the published ``config.json`` names: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``vocab_size``, ``sliding_window``,
``gated_mlp``, ``tie_word_embeddings``).

What is counted, and what is not:

* matmul work only (2 FLOPs per multiply-add): QKV, attention output, MLP,
  the LM head; QK^T and AV over the keys a causal query may see, capped at
  the sliding window;
* no embedding gather (a gather is not matmul work, tied or untied), no
  norms, no softmax, no recomputation (selective or full remat re-does
  forward work; MFU counts what the mathematics needs once);
* backward = 2 x forward (a gradient for each matmul operand).
"""

from __future__ import annotations

from typing import Dict, Optional

BF16 = 2  # bytes


def _dims(model: Dict):
    h = int(model["hidden_size"])
    n = int(model["num_attention_heads"])
    nkv = int(model["num_key_value_heads"])
    d = int(model.get("head_dim") or h // n)
    ffn = int(model["intermediate_size"])
    layers = int(model["num_hidden_layers"])
    vocab = int(model["vocab_size"])
    return h, n, nkv, d, ffn, layers, vocab


def layer_matmul_params(model: Dict) -> int:
    """Weights of one block that take part in a matmul."""
    h, n, nkv, d, ffn, _, _ = _dims(model)
    mlp = (3 if model.get("gated_mlp") else 2) * h * ffn
    return h * (n + 2 * nkv) * d + n * d * h + mlp


def matmul_params(model: Dict) -> int:
    """Every weight that multiplies an activation: the blocks and the head.
    The input embedding is a gather and is not here; a tied head is the
    same table used as a matmul once, so it counts once."""
    h, _, _, _, _, layers, vocab = _dims(model)
    return layers * layer_matmul_params(model) + h * vocab


def total_params(model: Dict) -> int:
    """Parameters held in memory (for sizing, not for FLOPs)."""
    h, _, _, _, _, _, vocab = _dims(model)
    emb = 0 if model.get("tie_word_embeddings") else h * vocab
    return matmul_params(model) + emb


def causal_keys(seq: int, window: Optional[int]) -> int:
    """Sum over the queries 0..seq-1 of the keys each may attend to:
    min(i + 1, window)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (seq - w) * w


def attention_flops_fwd(model: Dict, seq: int) -> float:
    """QK^T and AV for one sequence through one layer, forward."""
    _, n, _, d, _, _, _ = _dims(model)
    return 4.0 * n * d * causal_keys(seq, model.get("sliding_window"))


def train_flops_per_token(model: Dict, seq: int) -> float:
    """Forward + backward matmul FLOPs per trained token at ``seq``."""
    _, _, _, _, _, layers, _ = _dims(model)
    fwd = 2.0 * matmul_params(model) + layers * attention_flops_fwd(model, seq) / seq
    return 3.0 * fwd


def flash_train_cost(model: Dict, seq: int, sequences: int,
                     layers: Optional[int] = None) -> Dict[str, float]:
    """FLOPs and HBM bytes the flash forward + backward kernels need for
    ``sequences`` sequences of ``seq`` tokens through ``layers`` layers.

    Forward: 2 matmuls (QK^T, PV).  Backward: 4 (dV, dP, dQ, dK); the
    recomputation of S inside the backward kernel is the algorithm's own
    choice and is not counted.  Bytes: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV (bf16, each once).
    """
    _, n, nkv, d, _, n_layers, _ = _dims(model)
    layers = n_layers if layers is None else layers
    fwd = attention_flops_fwd(model, seq)
    q_bytes = seq * n * d * BF16
    kv_bytes = seq * nkv * d * BF16
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes
    per = sequences * layers
    return {"flops": per * 3.0 * fwd, "bytes": per * float(fwd_bytes + bwd_bytes)}


def kv_bytes_per_token(model: Dict, layers: Optional[int] = None) -> int:
    """Bytes of K and V one cached token holds (bf16 pool)."""
    _, _, nkv, d, _, n_layers, _ = _dims(model)
    return 2 * nkv * d * BF16 * (n_layers if layers is None else layers)


def visible_keys(context: int, window: Optional[int]) -> int:
    """Keys one query at the end of ``context`` cached tokens must read."""
    return min(context, int(window)) if window else context


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]):
    """(least seconds the chip could take, which bound holds)."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
