"""Plain reference of the SmallThinker decoder (SmallThinker-21BA3B): pre-norm
residual blocks whose layer l, with input x, computes

    h   = RMSNorm(x; g1)
    r   = h W_r                         the router reads the ATTENTION'S input
    q, k, v = h W_q, h W_k, h W_v       GQA, no bias
    q, k <- RoPE(q, k)                  only where rope_layout[l] == 1
    mask = causal, and kv_pos > q_pos - window
                                        only where sliding_window_layout[l] == 1
    x1  = x + softmax(q k^T / sqrt(d) + mask) v W_o
    m   = RMSNorm(x1; g2)
    S   = top-k of r;  p = softmax over the k chosen logits
    y   = sum_{e in S, e held} p_e W_down,e (relu(W_gate,e m) * (W_up,e m))
    out = x1 + y

then a final RMSNorm and an untied head.  float32, ``highest`` matmul
precision, no kernels, no sort, no cache: every held expert runs on every
token and its weight (zero where the token did not choose it) masks the
result.  The layouts give ONE period; layer l is of kind ``l % period``.

The chip's share.  The router has ``router_width`` outputs and the choice is
over all of them; the parameter tree holds the experts ``first_held_expert``
.. ``+ moe_num_primary_experts`` only, and what the absent experts would add
is left out, as in the program.  With all experts held this is the whole
model (the CPU tests use it so).

It reads the *program's* parameter tree.  Departures from the published
modelling code, none of which changes a result:

* the fused QKV weight is group-major (for each KV head: its query heads,
  then K, then V) instead of three matrices (``common.split_qkv``);
* RoPE rotates interleaved pairs (``common.rope``); the checkpoint's
  rotate-half layout is the same rotation under a fixed permutation of a
  head's dims, which weight conversion applies;
* an expert's up and gate matrices are ``fc1[e, 0]`` and ``fc1[e, 1]`` of one
  ``[experts, 2, h, width]`` stack (the repo's GLU layout: up = half 0,
  gate = half 1);
* the family's "secondary experts" (on-device sparsity predictors) have no
  key in the config and take no part in the forward pass.

Memory.  Attention runs a block of queries at a time (``common.causal_attention``)
and the experts a block of ``EXPERT_BLOCK`` at a time, so that thousands of positions
fit beside a training state; a layer is cast to float32 when it runs.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EXPERT_BLOCK = 4


def router_weights(logits, k: int):
    """[tokens, router_width]: the published order: top-k of the LOGITS,
    softmax over the k chosen; zero for the experts not chosen."""
    top, chosen = jax.lax.top_k(logits, k)
    p = jax.nn.softmax(top, axis=-1)
    dense = jnp.zeros_like(logits)
    return dense.at[jnp.arange(logits.shape[0])[:, None], chosen].set(p)


def experts(p: Dict, x, w):
    """sum_e w[:, e] E_e(x) over the held experts: x [t, h], w [t, held].
    The stacks arrive in the weights' dtype and are cast a block at a time."""
    fc1, fc2 = (p[k]["kernel"] for k in ("fc1", "fc2"))
    held = fc1.shape[0]
    size = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else held

    def blocks(a):
        return a.reshape(held // size, size, *a.shape[1:])

    def one_block(acc, xs):
        f1, f2, wb = xs                     # [size, 2, h, f], [size, f, h]
        f1, f2 = f1.astype(c.F32), f2.astype(c.F32)
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        y = jnp.einsum("etf,efh->eth", jax.nn.relu(gate) * up, f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, jnp.zeros_like(x),
                          (blocks(fc1), blocks(fc2), blocks(w.T)))
    return out


def block(layer: Dict, x, model: Dict, place: int):
    """One layer at place ``place`` of its period; its leaves are float32
    but for the expert stacks (``experts`` casts those)."""
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    b, s, hid = x.shape
    h1 = c.rms_norm(x, layer["input_norm"]["scale"], eps)
    r = h1.reshape(b * s, hid) @ layer["moe"]["router"]["kernel"]
    q, k, v = c.split_qkv(h1 @ layer["attention"]["qkv"]["kernel"], n, nkv, d)
    if model["rope_layout"][place]:
        theta = float(model["rope_theta"])
        q, k = c.rope(q, theta), c.rope(k, theta)
    window = (model["sliding_window_size"]
              if model["sliding_window_layout"][place] else None)
    x1 = x + c.causal_attention(q, k, v, window) @ layer["attention"]["dense"]["kernel"]
    m = c.rms_norm(x1, layer["post_norm"]["scale"], eps)
    w = router_weights(r, model["moe_num_active_primary_experts"])
    first = int(model.get("first_held_expert", 0))
    held = layer["moe"]["experts"]["fc1"]["kernel"].shape[0]
    y = experts(layer["moe"]["experts"], m.reshape(b * s, hid),
                w[:, first:first + held])
    return x1 + y.reshape(b, s, hid)


def run_layers(params: Dict, x, model: Dict):
    layers = params["layers"]
    depth = jax.tree.leaves(layers)[0].shape[0]
    period = len(model["sliding_window_layout"])

    def step(layer, h, place):
        stacks = layer["moe"]["experts"]
        rest = c.f32({k: v for k, v in layer.items() if k != "moe"})
        rest["moe"] = {"router": c.f32(layer["moe"]["router"]),
                       "experts": stacks}
        return block(rest, h, model, place)

    step = jax.jit(step, static_argnums=2)
    for i in range(depth):
        x = step(jax.tree.map(lambda a: a[i], layers), x, i % period)
    return x


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"].astype(c.F32)[tokens]
        x = run_layers(params, x, model)
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          model["rms_norm_eps"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: untied."""
    return c.project(hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
