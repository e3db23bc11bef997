"""Plain reference of the A.X-K2 decoder (skt's ``axk2``: the DeepSeek-V3.2
block under two sublayers of the model's own).  Per layer

    x' = x + Attn(GN_1(x)),      y = x' + FFN(GN_2(x')),

after the last layer ``GN_f`` and an untied head.  Layer 0's FFN is a
SwiGLU MLP; every later layer's is the sum of the top-8 of 256 routed
experts (group-limited, bias-corrected sigmoid router) plus one shared
expert.  ``Attn`` is multi-head latent attention whose every query attends
the ``index_topk`` positions a learned indexer scores best and no other.
float32, ``highest`` matmul precision, the expanded form of the attention,
a full sort for the selection, a loop over the held experts under a mask;
no cache, no threshold, no kernel.

What the published config names but does not define is ONE function each
here (and one in the program), listed in the configuration's ``assumed``:

* :func:`gated_norm` — ``n * sigmoid((n W_down) W_up)``, ``n = RMSNorm(x)``
  (``gated_norm``, ``gated_norm_rank``), at a layer's two norms and the
  final norm, not at the latents' norms nor the index key's;
* :func:`head_gate` — one sigmoid value a HEAD on the attention's output,
  read from the layer's normed input (``attention_output_gate``; the
  parameter total decides head against element, PERF.md section 4);
* :func:`rope_pairs` — the attention's rope dims rotate as interleaved
  pairs (the family's layout, as ``joyai_block``) under YaRN with unscaled
  cos / sin and a softmax scale of ``m^2 / sqrt(192)``;
* :func:`rope_halves` — the indexer's first ``qk_rope_head_dim`` dims
  rotate as HALVES (the released V3.2 inference code), same frequencies;
* :func:`index_scores`, :func:`picked` — the DeepSeek-V3.2 indexer: ``I[t,
  s] = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)``, the ``index_topk`` largest,
  ties to the earlier position.  The released code's Hadamard rotation of
  ``q^I`` and ``k^I`` is orthogonal, cancels in the product and serves its
  fp8 only: left out.

It reads the *program's* parameter tree.  Layouts as ``joyai_block``;
``model["first_held_expert"]`` and the expert stacks' own length say which
share of the routed experts is held, and what the absent ones would add
is left out, as in the program.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EXPERT_BLOCK = 4


def gated_norm(x, p: Dict, model: Dict):
    n = c.rms_norm(x, p["scale"], model["rms_norm_eps"])
    return n * jax.nn.sigmoid((n @ p["gate_down"]) @ p["gate_up"])


def head_gate(p: Dict, u):
    """[b, s, heads]: the gate of each head's output."""
    return jax.nn.sigmoid(u @ p["g_proj"]["kernel"])


def rope_frequencies(model: Dict, d: int):
    """[d / 2]: YaRN's blend of the ``d``-wide rotation's frequencies."""
    rp = model["rope_parameters"]
    theta = float(rp["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if rp.get("factor", 1) > 1:
        orig = rp["original_max_position_embeddings"]

        def pair(turns):
            return d * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(pair(rp["beta_fast"])), 0)
        high = min(math.ceil(pair(rp["beta_slow"])), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
    return inv


def _angles(x, model: Dict):
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * rope_frequencies(
        model, d)[None, :]
    return (jnp.cos(ang).astype(c.F32)[None, :, None, :],
            jnp.sin(ang).astype(c.F32)[None, :, None, :])


def rope_pairs(x, model: Dict):
    """x [b, s, heads, d]; positions 0..s-1; dims (0,1), (2,3), ... pair."""
    cos, sin = _angles(x, model)
    xp = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = xp[..., 0], xp[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def rope_halves(x, model: Dict):
    """x [b, s, heads, d]; dim i pairs with dim i + d / 2."""
    cos, sin = _angles(x, model)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(model: Dict) -> float:
    rp = model["rope_parameters"]
    m = 1.0
    if rp.get("factor", 1) > 1 and rp.get("mscale_all_dim"):
        m = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0
    return m * m / math.sqrt(
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"])


def index_inputs(p: Dict, u, c_q, model: Dict):
    """(q^I [b, s, heads, dim], k^I [b, s, dim], w [b, s, heads])."""
    b, s, _ = u.shape
    hi, di = model["index_n_heads"], model["index_head_dim"]
    rd = model["qk_rope_head_dim"]
    q = (c_q @ p["index_q"]["kernel"]).reshape(b, s, hi, di)
    k = c.layer_norm(u @ p["index_k"]["kernel"], p["index_k_norm"]["scale"],
                     p["index_k_norm"]["bias"], model["rms_norm_eps"])
    rot = lambda t: jnp.concatenate(                             # noqa: E731
        [rope_halves(t[..., :rd], model), t[..., rd:]], -1)
    w = (u @ p["index_w"]["kernel"]) / math.sqrt(hi * di)
    return rot(q), rot(k[:, :, None])[:, :, 0], w


def index_activation(x):
    return jax.nn.relu(x)


def index_topk_of(model: Dict) -> int:
    """How many keys a query attends."""
    return int(model["index_topk"])


def router_groups(model: Dict):
    """(groups the experts stand in, groups that stay)."""
    return int(model.get("n_group", 1)), int(model.get("topk_group", 1))


def index_scores(q, k, w):
    """I [b, queries, keys] of index queries ``q`` [b, queries, heads, dim]
    with weights ``w`` against keys ``k`` [b, keys, dim]."""
    dots = jnp.einsum("bqhd,bkd->bqhk", q, k)
    return jnp.einsum("bqh,bqhk->bqk", w, index_activation(dots))


def picked(scores, allowed, k: int):
    """The mask [b, queries, keys] of the ``k`` largest ``scores`` among
    ``allowed`` a query, ties to the earlier key (a stable full sort);
    every allowed key where a query has at most ``k``."""
    keys = scores.shape[-1]
    if keys <= k:
        return allowed
    order = jnp.argsort(-jnp.where(allowed, scores, -jnp.inf), axis=-1,
                        stable=True)[..., :k]
    hit = jnp.zeros(scores.shape, bool)
    bi, qi = jnp.meshgrid(jnp.arange(scores.shape[0]),
                          jnp.arange(scores.shape[1]), indexing="ij")
    return hit.at[bi[..., None], qi[..., None], order].set(True) & allowed


def attention(p: Dict, u, model: Dict, tap=None):
    """``tap``: a list that receives each query block's selection mask
    (tests compare the program's sets with it)."""
    n = model["num_attention_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rd, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    eps = model["rms_norm_eps"]
    b, s, _ = u.shape
    c_q = c.rms_norm(u @ p["q_down"]["kernel"], p["q_norm"]["scale"], eps)
    q = (c_q @ p["q_up"]["kernel"]).reshape(b, s, n, nope + rd)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], model)], -1)
    ckv = u @ p["kv_down"]["kernel"]
    c_kv = c.rms_norm(ckv[..., :r], p["kv_norm"]["scale"], eps)
    k_rope = rope_pairs(ckv[..., None, r:], model)           # [b, s, 1, rd]
    kv = jnp.einsum("bsr,rnd->bsnd", c_kv, p["kv_up"]["kernel"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, n, rd))], -1)
    v = kv[..., nope:]
    q_i, k_i, w_i = index_inputs(p, u, c_q, model)
    scale = softmax_scale(model)

    def attend(qs, start):
        qb, qib, wib = qs
        size = qb.shape[1]
        causal = c.causal_mask(start, size, s, None)[None]
        keep = picked(index_scores(qib, k_i, wib),
                      jnp.broadcast_to(causal, (b, size, s)),
                      index_topk_of(model))
        if tap is not None:
            tap.append(keep)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        probs = jax.nn.softmax(
            jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, size, n * vd)

    # a block's scores: heads x keys of attention AND of the indexer
    size = c.query_block(s, n + model["index_n_heads"])
    if size >= s:
        ctx = attend((q, q_i, w_i), 0)
    else:
        blocks = -(-s // size)
        pad = lambda a: jnp.pad(                                 # noqa: E731
            a, ((0, 0), (0, blocks * size - s)) + ((0, 0),) * (a.ndim - 2))
        cut = lambda a, i: jax.lax.dynamic_slice_in_dim(         # noqa: E731
            a, i * size, size, axis=1)
        qp = tuple(pad(a) for a in (q, q_i, w_i))
        out = jax.lax.map(
            lambda i: attend(tuple(cut(a, i) for a in qp), i * size),
            jnp.arange(blocks))
        ctx = out.transpose(1, 0, 2, 3).reshape(b, blocks * size, -1)[:, :s]
    gate = jnp.repeat(head_gate(p, u), vd, axis=-1)          # [b, s, n * vd]
    return (ctx * gate) @ p["dense"]["kernel"]


def swiglu(x, fc1, fc2):
    """fc1 [h, 2, w]: up at 0, gate at 1."""
    return ((x @ fc1[:, 0, :]) * jax.nn.silu(x @ fc1[:, 1, :])) @ fc2


def router_weights(router: Dict, x, model: Dict):
    """[tokens, experts]: the weight of each of the router's experts for
    each token, zero for the ones it did not choose.  Selection on ``s +
    b``: a group's score is the sum of its two largest, the ``topk_group``
    best groups stay, the top-k is taken among their experts; the weights
    are ``s`` of the chosen over their sum, held here or not."""
    k = model["num_experts_per_tok"]
    groups, stay = router_groups(model)
    s = jax.nn.sigmoid(x @ router["kernel"])
    pick = s + router["bias"]
    if groups > 1:
        t, e = pick.shape
        by_group = pick.reshape(t, groups, e // groups)
        score = jax.lax.top_k(by_group, 2)[0].sum(-1)        # [t, groups]
        _, best = jax.lax.top_k(score, stay)
        alive = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        pick = jnp.where(jnp.repeat(alive, e // groups, axis=1), pick,
                         -jnp.inf)
    _, chosen = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * model["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def moe(p: Dict, x, model: Dict):
    """The held experts' part of sum_i w_i E_i(x), plus E_shared(x), for x
    [tokens, h].  The expert stacks arrive in the weights' dtype and are
    cast ``EXPERT_BLOCK`` experts at a time."""
    w = router_weights(p["router"], x, model)                # [t, E]
    out = swiglu(x, p["shared"]["fc1"]["kernel"], p["shared"]["fc2"]["kernel"])
    fc1, fc2 = (p["experts"][k]["kernel"] for k in ("fc1", "fc2"))
    held = fc1.shape[0]
    first = int(model.get("first_held_expert", 0))
    w = w[:, first:first + held]
    size = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else held

    def blocks(a):
        return a.reshape(held // size, size, *a.shape[1:])

    def one_block(acc, xs):
        f1, f2, wb = xs                     # [size, 2, h, f], [size, f, h]
        f1, f2 = f1.astype(c.F32), f2.astype(c.F32)
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        y = jnp.einsum("etf,efh->eth", up * jax.nn.silu(gate), f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, out,
                          (blocks(fc1), blocks(fc2), blocks(w.T)))
    return out


def block(layer: Dict, x, model: Dict, tap=None):
    """One layer whose leaves are float32 but for the expert stacks."""
    u = gated_norm(x, layer["input_norm"], model)
    h = x + attention(layer["attention"], u, model, tap)
    y = gated_norm(h, layer["post_norm"], model)
    if "mlp" in layer:
        return h + swiglu(y, layer["mlp"]["fc1"]["kernel"],
                          layer["mlp"]["fc2"]["kernel"])
    b, s, d = y.shape
    return h + moe(layer["moe"], y.reshape(b * s, d), model).reshape(b, s, d)


def _run_stack(layers: Dict, x, model: Dict, taps=None):
    """One layer at a time; everything but the expert stacks is cast to
    float32 here, the stacks inside ``moe``."""
    depth = jax.tree.leaves(layers)[0].shape[0]

    def step(layer, h, tap=None):
        experts = layer.get("moe", {}).get("experts")
        rest = c.f32({k: v for k, v in layer.items() if k != "moe"})
        if experts is not None:
            rest["moe"] = {**c.f32({k: v for k, v in layer["moe"].items()
                                    if k != "experts"}), "experts": experts}
        return block(rest, h, model, tap)

    jitted = jax.jit(step)
    for i in range(depth):
        layer = jax.tree.map(lambda a: a[i], layers)
        if taps is None:
            # wait for the layer before the next is sliced (joyai_block)
            x = jax.block_until_ready(jitted(layer, x))
        else:       # un-jitted, so that the masks come out as values
            taps.append([])
            x = step(layer, x, taps[-1])
    return x


def stack(params: Dict, tokens, model: Dict, taps=None):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32.
    ``taps``: a list that receives, a layer, the list of its query blocks'
    selection masks."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][tokens].astype(c.F32)
        x = _run_stack(params["dense_layers"], x, model, taps)
        x = _run_stack(params["layers"], x, model, taps)
        return gated_norm(x, c.f32(params["final_norm"]), model)


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: untied."""
    return c.project(hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
