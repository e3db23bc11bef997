"""Plain reference of the Llama-style decoder (Mistral-7B): pre-norm
residual blocks of RMSNorm -> GQA attention with RoPE and a sliding causal
window -> RMSNorm -> SwiGLU MLP; final RMSNorm; untied (or tied) head.
float32, ``highest`` matmul precision, straightforward ``jax.numpy``.
"""

from __future__ import annotations

from typing import Dict

import jax

from benchmark.reference import common as c


def block(layer: Dict, x, model: Dict):
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    h1 = c.rms_norm(x, layer["input_norm"]["scale"], eps)
    q, k, v = c.split_qkv(h1 @ layer["attention"]["qkv"]["kernel"], n, nkv, d)
    q, k = c.rope(q, model["rope_theta"]), c.rope(k, model["rope_theta"])
    ctx = c.causal_attention(q, k, v, model.get("sliding_window"))
    x = x + ctx @ layer["attention"]["dense"]["kernel"]
    h2 = c.rms_norm(x, layer["post_norm"]["scale"], eps)
    fc1 = layer["mlp"]["fc1"]["kernel"]            # [h, 2, ffn]: up, gate
    up, gate = h2 @ fc1[:, 0, :], h2 @ fc1[:, 1, :]
    return x + (up * jax.nn.silu(gate)) @ layer["mlp"]["fc2"]["kernel"]


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"].astype(c.F32)[tokens]
        x = c.run_layers(block, params, x, model)
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          model["rms_norm_eps"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32."""
    return c.project(hidden, params["embedding"]["word_embeddings"].T
                     if model.get("tie_word_embeddings")
                     else params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
