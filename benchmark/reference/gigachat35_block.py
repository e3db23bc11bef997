"""Plain reference of the GigaChat3.5 decoder (``gigachat3_5``): a hybrid
stack in which the layers named by ``full_attention_layers`` are gated
latent attention (DeepSeek-V2/V3's MLA with an elementwise output gate,
YaRN on the rope dims) and every other layer is a gated delta rule
(arXiv:2412.06464, in the form of ``Qwen3NextGatedDeltaNet``); the first
``first_k_dense_replace`` layers carry a dense SwiGLU, every later one the
DeepSeek-V3 expert layer (top-8 of 256 by a bias-corrected sigmoid router,
one shared expert), of whose routed experts this chip HOLDS a share; a norm
before and after each sublayer (``pre_post``); final norm; untied head.

float32 at ``highest`` matmul precision, no cache, no kernel: the linear
layers by the RECURRENT form a token at a time (the state is never chunked
and no triangle is solved), latent attention in the EXPANDED form (per-head
keys and values made from the latent), every held expert on every token
under a mask.

What the published config does not fix is one function each, so that
another reading of the modelling file changes one line (``assumed`` in the
configuration file says what each follows; ``tools/serve_faults.py``
flips them):

* :func:`norm_gain`: ``2 sigmoid(w)`` (``ZeroCenteredGatedNorm``,
  ``layernorm_gating_weight`` 2) | ``1 + w``;
* :func:`post_norms`: the after-norms of ``layernorm_type: pre_post``
  inside the residual branch;
* :func:`softmax_scale`: ``(nope + rope)^-0.5 m^2`` (``use_mla_scaling_
  factor``, the DeepSeek-V3 convention);
* :func:`attention_gate`: ``sigmoid(u W_g)`` on the attention's output;
* :func:`delta_decay`, :func:`write_strength`, :func:`output_gate_scale`,
  :func:`conv_reset_every`: the linear layer's decay, beta, the 2 of its
  output gate, and (a fault only) a convolution that loses its tail;
* :func:`swiglu_limit`: the clamp, dense MLP and experts alike.

It reads the *program's* parameter tree: the dense prefix is
``params["dense_layers"]`` (linear layers), the expert layers
``params["layers"]`` without a mixer, and the mixers two stacks of their
own, ``params["mixers"]["attention" | "delta"]``, each over its own layers
in order.  RoPE rotates interleaved pairs (``rope_interleave: true``).  The
MTP modules are absent.  ``model["first_held_expert"]`` and the expert
stacks' own length say which share of the routed experts is held; what the
absent ones would add is left out, as in the program.

Memory.  A layer's sublayer is a program of its own that reads its weights
out of the stacks where they lie; inside it one matrix of the dense MLP and
``EXPERT_BLOCK`` experts are cast at a time.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EXPERT_BLOCK = 4


# ---- the choices the config does not fix ------------------------------------

def norm_gain(w, model: Dict):
    return model.get("layernorm_gating_weight", 2) * jax.nn.sigmoid(w)


def post_norms(model: Dict) -> bool:
    return model.get("layernorm_type") == "pre_post"


def softmax_scale(model: Dict) -> float:
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling") or {}
    if model.get("use_mla_scaling_factor") and rs.get("factor", 1) > 1:
        m = 0.1 * rs.get("mscale_all_dim", 0) * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def attention_gate(p: Dict, u):
    return jax.nn.sigmoid(u @ p["g_proj"]["kernel"])


def delta_decay(p: Dict, u, hv: int):
    a = (u @ p["ba"]["kernel"])[..., hv:]
    return -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])


def write_strength(p: Dict, u, hv: int):
    return jax.nn.sigmoid((u @ p["ba"]["kernel"])[..., :hv])


def output_gate_scale(model: Dict) -> float:
    return float(model.get("linear_sigmoid_gate_scale", 2))


def conv_reset_every(model: Dict):
    """Positions after which the convolution forgets its last inputs: None,
    never (a planted fault gives a tick's boundary)."""
    return None


def swiglu_limit(model: Dict):
    return model.get("swiglu_limit")


# ---- pieces -----------------------------------------------------------------

def norm(x, p: Dict, model: Dict):
    """A norm leaf ``{'gate': w}`` of the program's tree."""
    w = p["gate"].astype(c.F32)
    return c.rms_norm(x, norm_gain(w, model), model["rms_norm_eps"])


def swiglu(x, fc1, fc2, model: Dict):
    """fc1 [h, 2, w]: up at 0, gate at 1; a matrix cast at a time."""
    up = x @ fc1[:, 0, :].astype(c.F32)
    gate = x @ fc1[:, 1, :].astype(c.F32)
    limit = swiglu_limit(model)
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (up * jax.nn.silu(gate)) @ fc2.astype(c.F32)


def yarn_rope(x, model: Dict):
    """x [b, s, heads, d]; positions 0..s-1; interleaved pairs; the
    frequencies blended as YaRN has them, cos and sin unscaled."""
    b, s, n, d = x.shape
    theta = float(model["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = model.get("rope_scaling") or {}
    if rs.get("factor", 1) > 1:
        orig = rs["original_max_position_embeddings"]

        def pair(turns):
            return d * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(pair(rs["beta_fast"])), 0)
        high = min(math.ceil(pair(rs["beta_slow"])), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        inv = inv / rs["factor"] * ramp + inv * (1.0 - ramp)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(c.F32)[None, :, None, :]
    sin = jnp.sin(ang).astype(c.F32)[None, :, None, :]
    xp = x.reshape(b, s, n, d // 2, 2)
    even, odd = xp[..., 0], xp[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(b, s, n, d)


def mla(p: Dict, u, model: Dict):
    """Latent attention on the normed input ``u`` [b, s, h], expanded."""
    n = model["num_attention_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rd, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    b, s, _ = u.shape
    c_q = norm(u @ p["q_down"]["kernel"], p["q_norm"], model)
    q = (c_q @ p["q_up"]["kernel"]).reshape(b, s, n, nope + rd)
    q = jnp.concatenate([q[..., :nope], yarn_rope(q[..., nope:], model)], -1)
    ckv = u @ p["kv_down"]["kernel"]
    c_kv = norm(ckv[..., :r], p["kv_norm"], model)
    k_rope = yarn_rope(ckv[..., None, r:], model)            # [b, s, 1, rd]
    kv = jnp.einsum("bsr,rnd->bsnd", c_kv, p["kv_up"]["kernel"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, n, rd))], -1)
    v = kv[..., nope:]
    scale = c.F32(softmax_scale(model))

    def attend(qb, start):
        size = qb.shape[1]
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        causal = c.causal_mask(start, size, s, None)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, size, n * vd)

    size = c.query_block(s, n)
    ctx = attend(q, 0) if size >= s else c.in_query_blocks(attend, q, size)
    if model.get("gated_attention"):
        ctx = ctx * attention_gate(p, u)
    return ctx @ p["dense"]["kernel"]


def conv(x, w, model: Dict):
    """Causal depthwise: x [b, s, ch], w [width, ch], w[-1] on the current
    input, zeros before the sequence."""
    width, s = w.shape[0], x.shape[1]
    at = jnp.arange(s)
    every = conv_reset_every(model)
    y = x * w[width - 1]
    for j in range(1, width):
        back = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :s]
        if every:
            back = jnp.where((at % every >= j)[None, :, None], back, 0.0)
        y = y + back * w[width - 1 - j]
    return y


def delta(p: Dict, u, model: Dict):
    """The gated delta rule on the normed input ``u``, a token at a time."""
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    qk, vz = hk * dk, hv * dv
    b, s, _ = u.shape
    qkvz = u @ p["qkvz"]["kernel"]
    mixed = jax.nn.silu(conv(qkvz[..., :2 * qk + vz], p["conv"]["kernel"],
                             model))
    z = qkvz[..., 2 * qk + vz:].reshape(b, s, hv, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(mixed[..., :qk].reshape(b, s, hk, dk)) * c.F32(dk ** -0.5)
    k = unit(mixed[..., qk:2 * qk].reshape(b, s, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    v = mixed[..., 2 * qk:].reshape(b, s, hv, dv)
    g, beta = delta_decay(p, u, hv), write_strength(p, u, hv)

    def one(state, xs):                        # state [b, hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] * (
            b_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = jax.lax.scan(one, jnp.zeros((b, hv, dk, dv), c.F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                  # [b, s, hv, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + model.get("linear_attn_o_norm_eps", 1e-6))
    o = o * (1.0 + p["o_norm"]["weight"]) * (
        output_gate_scale(model) * jax.nn.sigmoid(z))
    return o.reshape(b, s, vz) @ p["dense"]["kernel"]


def router_weights(router: Dict, x, model: Dict):
    """[tokens, experts]: the weight of each of the router's experts for
    each token, zero for the ones it did not choose; normalised over ALL
    the chosen, held here or not."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ router["kernel"])
    _, chosen = jax.lax.top_k(s + router["bias"], k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * model["routed_scaling_factor"]
    dense = jnp.zeros_like(s)
    return dense.at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def moe(p: Dict, x, model: Dict, layer=None):
    """The held experts' part of sum_i w_i E_i(x), plus E_shared(x), for x
    [tokens, h].  ``p["experts"]`` arrives in the weights' dtype: one
    layer's ``[held, ...]`` stacks, or with ``layer`` the whole ``[layers,
    held, ...]`` stacks, of which ``EXPERT_BLOCK`` experts of that layer are
    sliced out and cast at a time (a layer's slice is a 1.4 GB copy at the
    published widths)."""
    w = router_weights(p["router"], x, model)                # [t, E]
    out = swiglu(x, p["shared"]["fc1"]["kernel"], p["shared"]["fc2"]["kernel"],
                 model)
    fc1, fc2 = (p["experts"][k]["kernel"] for k in ("fc1", "fc2"))
    if layer is None:
        fc1, fc2, layer = fc1[None], fc2[None], 0
    held = fc1.shape[1]
    first = int(model.get("first_held_expert", 0))
    w = w[:, first:first + held]
    size = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else held
    limit = swiglu_limit(model)

    def block_of(a, b):
        at = (layer, b * size) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_slice(
            a, at, (1, size) + a.shape[2:])[0].astype(c.F32)

    def one_block(acc, b):
        f1, f2 = block_of(fc1, b), block_of(fc2, b)  # [size, 2, h, f], [.., f, h]
        wb = jax.lax.dynamic_slice_in_dim(w, b * size, size, axis=1).T
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        if limit is not None:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        y = jnp.einsum("etf,efh->eth", up * jax.nn.silu(gate), f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, out, jnp.arange(held // size))
    return out


def _at(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def mixer_part(norms: Dict, mixers: Dict, x, model: Dict, i: int, j: int,
               linear: bool):
    """x + [N2] Mixer(N1(x)) of the layer whose norms are ``norms[..][i]``
    and whose mixer is layer ``j`` of the stack ``mixers``; both are sliced
    and cast here, inside the layer's own program."""
    u = norm(x, _at(norms["input_norm"], i), model)
    att = c.f32(_at(mixers, j))
    out = delta(att, u, model) if linear else mla(att, u, model)
    if post_norms(model):
        out = norm(out, _at(norms["attn_out_norm"], i), model)
    return x + out


def ffn_part(layers: Dict, x, model: Dict, i: int):
    """x + [N4] FFN(N3(x)) of layer ``i`` of the stack ``layers``: the dense
    MLP or the expert layer."""
    y = norm(x, _at(layers["post_norm"], i), model)
    if "mlp" in layers:
        mlp = _at(layers["mlp"], i)
        out = swiglu(y, mlp["fc1"]["kernel"], mlp["fc2"]["kernel"], model)
    else:
        b, s, d = y.shape
        p = layers["moe"]
        small = c.f32(_at({k: v for k, v in p.items()
                           if k not in ("experts", "shared")}, i))
        out = moe({**small, "experts": p["experts"],
                   "shared": _at(p["shared"], i)},
                  y.reshape(b * s, d), model, layer=i).reshape(b, s, d)
    if post_norms(model):
        out = norm(out, _at(layers["mlp_out_norm"], i), model)
    return x + out


def layer_plan(params: Dict, model: Dict):
    """(the stack a layer lies in, its place there, its mixer's stack, its
    place there, linear?) of every layer a token passes, in order.  Places
    only: a layer's slice of a stack made out here would be a copy (1.5 GB
    of held experts at the published widths) beside everything else."""
    full = set(model["full_attention_layers"])
    dense = params.get("dense_layers")
    n_dense = 0 if dense is None else jax.tree.leaves(dense)[0].shape[0]
    n_rest = jax.tree.leaves(params["layers"])[0].shape[0]
    seen = {"attention": 0, "delta": 0}
    for i in range(n_dense + n_rest):
        linear = i not in full
        if i < n_dense:
            rest = {k: v for k, v in dense.items() if k != "attention"}
            yield rest, i, dense["attention"], i, linear
        else:
            kind = "delta" if linear else "attention"
            yield (params["layers"], i - n_dense, params["mixers"][kind],
                   seen[kind], linear)
            seen[kind] += 1


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32.
    A program a layer and sublayer (its places in the stacks are static),
    one at a time."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][tokens].astype(c.F32)
        around = ("input_norm", "attn_out_norm")      # the mixer's norms
        for layers, i, mixers, j, linear in layer_plan(params, model):
            x = jax.block_until_ready(jax.jit(
                lambda n, m, h, i=i, j=j, linear=linear: mixer_part(
                    n, m, h, model, i, j, linear))(
                {k: layers[k] for k in around if k in layers}, mixers, x))
            x = jax.block_until_ready(jax.jit(
                lambda p, h, i=i: ffn_part(p, h, model, i))(
                {k: v for k, v in layers.items() if k not in around}, x))
        return norm(x, params["final_norm"], model)


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: untied."""
    return c.project(hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
