"""Plain reference of the Falcon-7B decoder: one LayerNorm feeds attention
(multi-query, RoPE) and a GELU MLP *in parallel*, both added to the
residual; final LayerNorm; head tied to the embedding.  float32,
``highest`` matmul precision.  GELU is the exact (erf) form of the
published modelling code.
"""

from __future__ import annotations

from typing import Dict

import jax

from benchmark.reference import common as c


def block(layer: Dict, x, model: Dict):
    n, nkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    ln = c.layer_norm(x, layer["input_norm"]["scale"],
                      layer["input_norm"]["bias"], model["layer_norm_epsilon"])
    q, k, v = c.split_qkv(ln @ layer["attention"]["qkv"]["kernel"], n, nkv, d)
    q, k = c.rope(q, model["rope_theta"]), c.rope(k, model["rope_theta"])
    attn = c.causal_attention(q, k, v, None) @ layer["attention"]["dense"]["kernel"]
    mlp = (jax.nn.gelu(ln @ layer["mlp"]["fc1"]["kernel"], approximate=False)
           @ layer["mlp"]["fc2"]["kernel"])
    return x + attn + mlp


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"].astype(c.F32)[tokens]
        x = c.run_layers(block, params, x, model)
        return c.layer_norm(x, params["final_norm"]["scale"].astype(c.F32),
                            params["final_norm"]["bias"].astype(c.F32),
                            model["layer_norm_epsilon"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab]: the embedding, tied."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda a, w: a @ w.astype(c.F32).T)(
            hidden, params["embedding"]["word_embeddings"])


def logits(params: Dict, tokens, model: Dict):
    return head(params, stack(params, tokens, model), model)
