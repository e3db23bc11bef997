"""Plain reference of the JoyAI-LLM-Flash decoder (the DeepSeek-V3 block):
pre-norm residual blocks of RMSNorm -> multi-head latent attention ->
RMSNorm -> FFN, where the FFN of the leading ``first_k_dense_replace``
layers is a SwiGLU MLP and that of every later layer is the sum of the
top-8 of 256 routed experts, picked by a bias-corrected sigmoid router,
plus one shared expert; final RMSNorm; untied head.  float32, ``highest``
matmul precision, the expanded form of the attention (per-head keys and
values made from the latent), a plain loop over the experts with a mask,
no cache, no sort, no kernel.

It reads the *program's* parameter tree (same weights: the comparison is
of arithmetic).  Departures from the published modelling code, none of
which changes a result:

* RoPE rotates interleaved pairs (dims 0-1, 2-3, ...), the repo's layout
  (``common.rope``); the checkpoint's ``rope_interleave: true`` names the
  same pairing, and a rotate-half checkpoint is the same rotation under a
  fixed permutation of the 64 rope dims, which weight conversion applies;
* the MTP layer (``num_nextn_predict_layers`` 1) is absent: it takes no
  part in next-token logits and the serving path does not load it;
* layouts: ``kv_up`` holds ``kv_b_proj`` as ``[latent, head, nope + v]``;
  a routed expert's up and gate matrices are ``fc1[e, 0]`` and ``fc1[e, 1]``
  of one ``[experts, 2, h, width]`` stack, the dense MLP's and the shared
  expert's one ``fc1`` ``[h, 2, width]`` with up at index 0 and gate at
  index 1 (the repo's GLU layouts); the dense prefix is the stack
  ``params["dense_layers"]``, the expert layers ``params["layers"]``;
* ``n_group`` = ``topk_group`` = 1: group-limited routing is the identity
  and is not written out.

Memory.  Beside 11 GB of bf16 weights a float32 copy of the tree (22 GB)
cannot exist, so one layer is cast at a time (``_run_stack``), and
inside an expert layer one block of ``EXPERT_BLOCK`` experts at a time.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EXPERT_BLOCK = 16


def swiglu(x, fc1, fc2):
    """fc1 [h, 2, w]: up at 0, gate at 1."""
    return ((x @ fc1[:, 0, :]) * jax.nn.silu(x @ fc1[:, 1, :])) @ fc2


def mla(p: Dict, x, model: Dict):
    n = model["num_attention_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rd, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    b, s, _ = x.shape
    c_q = c.rms_norm(x @ p["q_down"]["kernel"], p["q_norm"]["scale"], eps)
    q = (c_q @ p["q_up"]["kernel"]).reshape(b, s, n, nope + rd)
    q = jnp.concatenate([q[..., :nope], c.rope(q[..., nope:], theta)], -1)
    ckv = x @ p["kv_down"]["kernel"]
    c_kv = c.rms_norm(ckv[..., :r], p["kv_norm"]["scale"], eps)
    k_rope = c.rope(ckv[..., None, r:], theta)               # [b, s, 1, rd]
    kv = jnp.einsum("bsr,rnd->bsnd", c_kv, p["kv_up"]["kernel"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, n, rd))], -1)
    v = kv[..., nope:]

    def attend(qb, start):
        size = qb.shape[1]
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / jnp.sqrt(c.F32(nope + rd))
        causal = c.causal_mask(start, size, s, None)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, size, n * vd)

    size = c.query_block(s, n)     # past a block of positions: a block at a time
    ctx = attend(q, 0) if size >= s else c.in_query_blocks(attend, q, size)
    return ctx @ p["dense"]["kernel"]


def router_weights(router: Dict, x, model: Dict):
    """[tokens, experts]: the weight of each expert for each token, zero
    for the experts it did not choose."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ router["kernel"])
    _, chosen = jax.lax.top_k(s + router["bias"], k)         # bias picks ...
    w = jnp.take_along_axis(s, chosen, axis=-1)              # ... not weighs
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * model["routed_scaling_factor"]
    dense = jnp.zeros_like(s)
    return dense.at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def moe(p: Dict, x, model: Dict):
    """sum_i w_i E_i(x) + E_shared(x) for x [tokens, h]: EVERY expert runs
    on every token and its weight (zero where the token did not choose it)
    masks the result.  The expert stacks arrive in the weights' dtype and
    are cast a block of ``EXPERT_BLOCK`` experts at a time; everything else
    in ``p`` is float32 already."""
    w = router_weights(p["router"], x, model)                # [t, E]
    out = swiglu(x, p["shared"]["fc1"]["kernel"], p["shared"]["fc2"]["kernel"])
    fc1, fc2 = (p["experts"][k]["kernel"] for k in ("fc1", "fc2"))
    n_experts = fc1.shape[0]
    size = EXPERT_BLOCK if n_experts % EXPERT_BLOCK == 0 else n_experts

    def blocks(a):
        return a.reshape(n_experts // size, size, *a.shape[1:])

    def one_block(acc, xs):
        f1, f2, wb = xs                     # [size, 2, h, f], [size, f, h]
        f1, f2 = f1.astype(c.F32), f2.astype(c.F32)
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        y = jnp.einsum("etf,efh->eth", up * jax.nn.silu(gate), f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, out, (blocks(fc1), blocks(fc2),
                                           blocks(w.T)))
    return out


def block(layer: Dict, x, model: Dict):
    """One layer whose leaves are float32 but for the expert stacks
    (``moe`` casts those)."""
    eps = model["rms_norm_eps"]
    h = x + mla(layer["attention"],
                c.rms_norm(x, layer["input_norm"]["scale"], eps), model)
    y = c.rms_norm(h, layer["post_norm"]["scale"], eps)
    if "mlp" in layer:
        return h + swiglu(y, layer["mlp"]["fc1"]["kernel"],
                          layer["mlp"]["fc2"]["kernel"])
    b, s, d = y.shape
    return h + moe(layer["moe"], y.reshape(b * s, d), model).reshape(b, s, d)


def _run_stack(layers: Dict, x, model: Dict):
    """One layer at a time; everything but the expert stacks is cast to
    float32 here, the stacks inside ``moe``."""
    depth = jax.tree.leaves(layers)[0].shape[0]

    def step(layer, h):
        experts = layer.get("moe", {}).get("experts")
        rest = c.f32({k: v for k, v in layer.items() if k != "moe"})
        if experts is not None:
            rest["moe"] = {**c.f32({k: v for k, v in layer["moe"].items()
                                    if k != "experts"}), "experts": experts}
        return block(rest, h, model)

    step = jax.jit(step)
    for i in range(depth):
        # a layer's slice of the expert stacks is a 2.4 GB copy at the
        # published widths: wait for the layer before the next is sliced, or
        # the host runs ahead and several copies are live at once
        x = jax.block_until_ready(step(jax.tree.map(lambda a: a[i], layers), x))
    return x


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"].astype(c.F32)[tokens]
        x = _run_stack(params["dense_layers"], x, model)
        x = _run_stack(params["layers"], x, model)
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          model["rms_norm_eps"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: untied."""
    return c.project(hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
