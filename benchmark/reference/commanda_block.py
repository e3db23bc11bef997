"""Plain reference of the Command A+ decoder (CohereLabs
command-a-plus-05-2026, ``cohere2_moe``): PARALLEL residual blocks whose
layer l, with input x, computes

    h   = (x - mean x) / sqrt(var x + eps) * g       LayerNorm, no bias
    q, k, v = h W_q, h W_k, h W_v                    GQA, no bias, no q/k norm
    window layer (layer_types[l] == "sliding_attention"):
        q, k <- RoPE(q, k)   all head_dim dims, interleaved pairs (2i, 2i+1)
        key j visible to query i  iff  0 <= i - j < sliding_window
    full layer ("full_attention"):
        no rotation, no position signal of any kind;  j <= i
    o   = softmax(q k^T / sqrt(head_dim)) v W_o
    s   = sigmoid(h W_r)                             float32, num_experts wide
    T   = the num_experts_per_tok largest of s;  w_e = s_e / sum_{T} s
    y   = sum_{e in T, e held} w_e W_down,e (silu(h W_gate,e) * (h W_up,e))
          + 1/S sum_{j < S} S_j(h)                   S shared SwiGLU MLPs, AVERAGED
    x'  = x + o + y

then the same LayerNorm form and the TIED head, ``logits = logit_scale *
LN(x) E^T``.  float32, ``highest`` matmul precision, no kernels, no sort, no
cache: every held expert runs on every token and its weight (zero where the
token did not choose it) masks the result.

The chip's share.  The router has ``num_experts`` outputs and the choice and
the normalisation are over all of them; the parameter tree holds the experts
``first_held_expert`` .. ``+ held`` only, and what the absent experts would
add is left out, as in the program.  With all experts held this is the whole
model (the CPU tests use it so, and sum the shares).

It reads the *program's* parameter tree.  Departures from the published
modelling code, none of which changes a result:

* the fused QKV weight is group-major (``common.split_qkv``);
* an expert's up and gate matrices are ``fc1[e, 0]`` and ``fc1[e, 1]`` of one
  ``[experts, 2, h, width]`` stack (the repo's GLU layout);
* the ``S`` shared experts are one MLP of ``S x width`` in the tree (``fc1``
  ``[h, 2, S * width]``, ``fc2`` ``[S * width, h]``): shared expert ``j`` is
  its columns / rows ``[j * width, (j + 1) * width)``.

The three choices a planted fault turns (``tools/serve_faults.py`` patches
them): :func:`window_of`, :func:`rotates`, :func:`shared_scale`.

Memory.  Attention runs a block of queries at a time
(``common.causal_attention``); the experts are cast to float32 ONE at a
time, routed and shared alike (a layer's 16 held experts are 3.2 GB in
float32, one is 0.2); a routed expert is sliced straight out of the whole
stack, and one layer's slice of the rest (0.7 GB) is alive at a time, so that
the comparison fits beside 9.5 GB of weights and the pools.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import common as c


def window_of(model: Dict, layer_type: str) -> Optional[int]:
    """Keys a query of this kind of layer may see (None = all before it)."""
    return (int(model["sliding_window"])
            if layer_type == "sliding_attention" else None)


def rotates(model: Dict, layer_type: str) -> bool:
    """Whether q and k of this kind of layer carry RoPE."""
    return layer_type == "sliding_attention"


def shared_scale(model: Dict) -> float:
    """What the shared experts' summed output is multiplied by."""
    if model["shared_expert_combination_strategy"] == "average":
        return 1.0 / int(model["num_shared_experts"])
    return 1.0


def router_weights(scores, k: int):
    """[tokens, num_experts]: the k largest scores each divided by their
    sum; zero for the experts not chosen."""
    top, chosen = jax.lax.top_k(scores, k)
    w = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(w)


def swiglu(x, up, gate, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed(p: Dict, i, x, w):
    """sum_e w[:, e] E_e(x) over layer ``i``'s held experts: x [t, h], w
    [t, held].  ``p`` holds the WHOLE ``[layers, held, ...]`` stacks in the
    weights' dtype: one expert of one layer is sliced out and cast at a
    time, so no layer's stack (1.6 GB at the published widths) is copied."""
    fc1, fc2 = p["fc1"]["kernel"], p["fc2"]["kernel"]

    def one(acc, xs):
        e, we = xs                              # [], [t]
        f1 = jax.lax.dynamic_slice(
            fc1, (i, e, 0, 0, 0), (1, 1) + fc1.shape[2:])[0, 0].astype(c.F32)
        f2 = jax.lax.dynamic_slice(
            fc2, (i, e, 0, 0), (1, 1) + fc2.shape[2:])[0, 0].astype(c.F32)
        return acc + we[:, None] * swiglu(x, f1[0], f1[1], f2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (jnp.arange(fc1.shape[1]), w.T))
    return out


def shared(p: Dict, x, n: int):
    """sum_{j < n} S_j(x), a shared expert at a time."""
    h, _, wide = p["fc1"]["kernel"].shape
    f = wide // n
    fc1 = p["fc1"]["kernel"].reshape(h, 2, n, f).transpose(2, 1, 0, 3)
    fc2 = p["fc2"]["kernel"].reshape(n, f, h)

    def one(acc, xs):
        f1, f2 = xs[0].astype(c.F32), xs[1].astype(c.F32)
        return acc + swiglu(x, f1[0], f1[1], f2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (fc1, fc2))
    return out


def block(layer: Dict, experts: Dict, i, x, model: Dict, layer_type: str):
    """Layer ``i``; ``layer``'s leaves are float32 but for the shared
    experts' stack, and ``experts`` is every layer's routed stacks
    (``routed`` / ``shared`` cast those)."""
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["layer_norm_eps"]
    b, s, hid = x.shape
    h = c.layer_norm(x, layer["input_norm"]["scale"], 0.0, eps)
    q, k, v = c.split_qkv(h @ layer["attention"]["qkv"]["kernel"], n, nkv, d)
    if rotates(model, layer_type):
        theta = float(model["rope_theta"])
        q, k = c.rope(q, theta), c.rope(k, theta)
    o = c.causal_attention(q, k, v, window_of(model, layer_type)) \
        @ layer["attention"]["dense"]["kernel"]
    moe = layer["moe"]
    ht = h.reshape(b * s, hid)
    scores = jax.nn.sigmoid(ht @ moe["router"]["kernel"])
    w = router_weights(scores, int(model["num_experts_per_tok"]))
    first = int(model.get("first_held_expert", 0))
    held = experts["fc1"]["kernel"].shape[1]
    y = routed(experts, i, ht, w[:, first:first + held])
    y = y + shared_scale(model) * shared(
        moe["shared"], ht, int(model["num_shared_experts"]))
    return x + o + y.reshape(b, s, hid)


def run_layers(params: Dict, x, model: Dict):
    layers = params["layers"]
    depth = jax.tree.leaves(layers)[0].shape[0]
    types = model["layer_types"]
    experts = layers["moe"]["experts"]
    rest = {k: v for k, v in layers.items() if k != "moe"}
    rest["moe"] = {k: v for k, v in layers["moe"].items() if k != "experts"}

    def step(layer, experts, i, h, layer_type):
        moe = layer["moe"]
        layer = c.f32({k: v for k, v in layer.items() if k != "moe"})
        layer["moe"] = {"router": c.f32(moe["router"]),
                        "shared": moe["shared"]}
        return block(layer, experts, i, h, model, layer_type)

    step = jax.jit(step, static_argnums=4)
    for i in range(depth):
        # a layer's slice of the stack is a copy (0.7 GB at the published
        # widths without the routed experts, which are never sliced by
        # layer): wait for the layer before the next one's is made, so that
        # one of them is alive at a time beside the weights and pools
        x = jax.block_until_ready(step(
            jax.tree.map(lambda a: a[i], rest), experts, jnp.int32(i), x,
            types[i % len(types)]))
    return x


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][tokens].astype(c.F32)
        x = run_layers(params, x, model)
        return c.layer_norm(x, params["final_norm"]["scale"].astype(c.F32),
                            0.0, model["layer_norm_eps"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: the TIED embedding."""
    scale = float(model.get("logit_scale", 1.0))
    return scale * c.project(hidden, params["embedding"]["word_embeddings"].T)


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
