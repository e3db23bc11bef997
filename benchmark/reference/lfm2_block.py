"""Plain reference of the LFM2 decoder (LiquidAI ``lfm2_moe``; LFM2-24B-A2B):
a stack in which layer ``l`` is a mixer and then a feed-forward, each behind
its own RMSNorm,

    h  = x + Mixer_l(RMSNorm_op(x));    x' = h + FFN_l(RMSNorm_ffn(h))

with a final RMSNorm and the head tied to the embedding.  ``layer_types[l]``
names the mixer, ``num_dense_layers`` the feed-forward.  With ``v`` the
normed input:

    conv:  [B | C | u] = v W_in                  (2048 -> 3 x 2048, no bias)
           g   = B * u
           c_t = sum_{j<3} w[2-j] g_{t-j}        causal, depthwise, zeros
                                                 before the sequence, no bias
           out = (C * c) W_out                   no activation anywhere
    full_attention:  32 query / 8 KV heads of 64; q and k each under ONE
           RMSNorm over a head's 64 values, THEN the rotary embedding (theta
           1e6, all 64 dims); causal softmax at 64^-0.5; W_o
    FFN, l <  num_dense_layers:  W_2 (SiLU(W_1 v) * W_3 v) at 11,776
    FFN, l >= num_dense_layers:  s = sigmoid(v W_r) in float32 over all 64;
           top-4 of s + expert_bias; w_i = s_i / (sum of the chosen s + 1e-6)
           x routed_scaling_factor; out = sum_i w_i E_i(v), E_i the same
           SwiGLU at 1,536; no shared expert

float32 at ``highest`` matmul precision, no cache, no kernel, no batching: the
convolution as a plain sum of shifted inputs over the whole sequence (a
sequence keeps nothing), every held expert on every token under a mask,
attention through ``common.causal_attention``.  It imports nothing from the
program.

Departures from the published modelling code, none of which changes a result:
it reads the *program's* parameter tree (``common.py``: group-major fused QKV;
RoPE on interleaved pairs, which is the checkpoints' rotate-half under a fixed
permutation of a head's dims that the head norms' scales take too;
``params["layers"]["input_norm"]`` holds the 80 norms, ``operator_norm`` of
layer ``l`` at ``2l`` and ``ffn_norm`` at ``2l + 1``; ``params["mixers"]
["conv" | "attention" | "mlp" | "experts"]`` a stack a kind over its own
layers in order; the in-projection's columns are B, C, u as published; the
conv's filter is ``[width, channels]`` with the last tap on the current input;
a GLU's ``fc1`` holds the value ``W_3`` at ``[..., 0, :]`` and the gated
``W_1`` at ``[..., 1, :]``); of the routed experts this chip HOLDS a share
(``model["first_held_expert"]`` and the stacks' own length say which), and
what the absent ones would add is left out, as in the program.

The choices a planted fault turns (``tools/serve_faults.py`` and
``tests/test_lfm2.py`` patch them), one function each: :func:`conv_taps`,
:func:`conv_lookahead`, :func:`in_gate`, :func:`out_gate`,
:func:`conv_restarts_every`, :func:`qk_normed`, :func:`rope_applied`,
:func:`weighed_scores`, :func:`gates_normalised`, :func:`gate_eps`,
:func:`dense_layers`, :func:`head_kernel`.  The dtype is ``common.F32``,
looked up at the call: ``benchmark/control.py`` patches that name for its
bfloat16 control.

The four layer programs are jitted ONCE, here (a ``jax.jit`` made anew at
every call is a new program to jax every time: PERF.md 7v).  What a trace
reads besides its operands is its first, static argument
(:func:`_traced_with`: the sizes, ``common.F32`` and the choices above as
they stand), so a patched choice or dtype is a trace of its own and the
plain one is found again afterwards.

Memory.  A layer program reads its weights out of the stacks where they lie,
a matrix (``EXPERT_BLOCK`` experts) cast at a time.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

EXPERT_BLOCK = 4


# ---- the choices ------------------------------------------------------------

def conv_taps(w):
    """[width, ch], ``w[-1]`` on the current input (``Conv1d`` weight
    ``[ch, 1, width]`` with left padding ``width - 1``)."""
    return w


def conv_lookahead(model: Dict) -> int:
    """Inputs AFTER the current one that a tap reads: none (causal)."""
    return 0


def in_gate(b, u):
    """What is convolved: ``B * u``."""
    return b * u


def out_gate(cg, y):
    """What is projected out: ``C * conv``."""
    return cg * y


def conv_restarts_every(model: Dict):
    """None: a token's conv reads the ``width - 1`` inputs before it wherever
    they lie.  N: the inputs before each multiple of N are taken as zeros
    (what a program would compute that dropped a sequence's tail between
    runs of N prompt rows; 1: that carried no tail at all, as every decode
    row is a run of its own)."""
    return None


def qk_normed(model: Dict) -> bool:
    """``q_layernorm`` / ``k_layernorm``: one RMSNorm a head, before RoPE."""
    return True


def rope_applied(model: Dict) -> bool:
    return True


def weighed_scores(s, biased):
    """The scores the chosen experts' weights are read from: the sigmoids
    WITHOUT ``expert_bias``, which only picks."""
    return s


def gates_normalised(model: Dict) -> bool:
    return bool(model.get("norm_topk_prob", True))


def gate_eps(model: Dict) -> float:
    """What the family's modelling code adds to the chosen scores' sum."""
    return 1e-6


def dense_layers(model: Dict) -> int:
    return int(model["num_dense_layers"])


def head_kernel(params: Dict):
    """[vocab, h]: the head is the embedding (the family's convention; the
    catalog's config carries no key for it)."""
    return params["embedding"]["word_embeddings"]


CHOICES = ("conv_taps", "conv_lookahead", "in_gate", "out_gate",
           "conv_restarts_every", "qk_normed", "rope_applied",
           "weighed_scores", "gates_normalised", "gate_eps", "dense_layers")
SIZES = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "hidden_size", "norm_eps", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "first_held_expert", "num_dense_layers",
         "rope_theta")


def _traced_with(model: Dict):
    """Hashable: everything a layer program's trace reads that is not an
    operand."""
    sizes = dict(model, rope_theta=(model.get("rope_parameters") or {}).get(
        "rope_theta", model.get("rope_theta", 1e6)))
    return (tuple((k, sizes[k]) for k in SIZES if k in sizes), c.F32,
            tuple(globals()[name] for name in CHOICES))


# ---- the sublayers ----------------------------------------------------------

def causal_conv(g, w, model: Dict):
    """g [b, s, ch]; ``y_t = sum_j w[j] g_{t - (width - 1 - j)}``."""
    w = conv_taps(w)
    width, s = w.shape[0], g.shape[1]
    ahead = conv_lookahead(model)
    every = conv_restarts_every(model)
    t = jnp.arange(s)
    y = 0.0
    for j in range(width):
        back = width - 1 - j - ahead              # how far behind t it reads
        src = t - back
        ok = (src >= 0) & (src < s)
        if every:
            ok &= (src // every) == (t // every)
        shifted = jnp.where(ok[None, :, None],
                            g[:, jnp.clip(src, 0, s - 1)], 0.0)
        y = y + shifted * w[j]
    return y


def short_conv(mix: Dict, v, model: Dict):
    """The gated short convolution on the normed input ``v`` [b, s, h]."""
    mix = c.f32(mix)
    h = v.shape[-1]
    bcu = v @ mix["in_proj"]["kernel"]
    b, cg, u = bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]
    g = in_gate(b, u).astype(c.F32)
    y = causal_conv(g, mix["conv"]["kernel"], model).astype(c.F32)
    return out_gate(cg, y) @ mix["dense"]["kernel"]


def attention(att: Dict, v, model: Dict):
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n
    att = c.f32(att)
    q, k, val = c.split_qkv(v @ att["qkv"]["kernel"], n, nkv, d)
    if qk_normed(model):
        eps = c.F32(model["norm_eps"])
        q = c.rms_norm(q, att["q_norm"]["scale"], eps)
        k = c.rms_norm(k, att["k_norm"]["scale"], eps)
    if rope_applied(model):
        theta = float(model["rope_theta"])
        q, k = c.rope(q, theta), c.rope(k, theta)
    return c.causal_attention(q, k, val, None) @ att["dense"]["kernel"]


def swiglu(x, fc1, fc2):
    """fc1 [h, 2, f]: the value at 0, the gated half at 1."""
    return (x @ fc1[:, 0] * jax.nn.silu(x @ fc1[:, 1])) @ fc2


def router_weights(router: Dict, x, model: Dict):
    """[tokens, experts]: the weight of each of the router's experts for each
    token, zero for the ones it did not choose; normalised over ALL the
    chosen, held here or not."""
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ router["kernel"])
    biased = s + router["bias"]
    _, chosen = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(weighed_scores(s, biased), chosen, axis=-1)
    if gates_normalised(model):
        w = w / (w.sum(-1, keepdims=True) + c.F32(gate_eps(model)))
    w = w * c.F32(model.get("routed_scaling_factor", 1.0))
    dense = jnp.zeros_like(s)
    return dense.at[jnp.arange(s.shape[0])[:, None], chosen].set(w)


def _at(tree, i):
    """Layer ``i`` (a traced index) of every leaf of a stack."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def experts(p: Dict, v, model: Dict, layer):
    """The held experts' part of sum_i w_i E_i(v) for ``v`` [b, s, h].  ``p``
    holds the whole ``[layers, ...]`` stacks in the weights' dtype;
    ``EXPERT_BLOCK`` experts of layer ``layer`` are sliced out and cast at a
    time."""
    b, s, d = v.shape
    x = v.reshape(b * s, d)
    w = router_weights(c.f32(_at(p["router"], layer)), x, model)   # [t, E]
    fc1, fc2 = (p["experts"][k]["kernel"] for k in ("fc1", "fc2"))
    held = fc1.shape[1]
    first = int(model.get("first_held_expert", 0))
    w = w[:, first:first + held]
    size = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else held

    def block_of(a, blk):
        at = (layer, blk * size) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_slice(
            a, at, (1, size) + a.shape[2:])[0].astype(c.F32)

    def one_block(acc, blk):
        f1, f2 = block_of(fc1, blk), block_of(fc2, blk)  # [e,2,h,f], [e,f,h]
        wb = jax.lax.dynamic_slice_in_dim(w, blk * size, size, axis=1).T
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        y = jnp.einsum("etf,efh->eth", up * jax.nn.silu(gate), f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, jnp.zeros_like(x),
                          jnp.arange(held // size))
    return out.reshape(b, s, d)


# ---- the layer programs, jitted once ---------------------------------------

def _normed(norms, i, h, model: Dict):
    return c.rms_norm(h, _at(norms, i)["scale"].astype(c.F32),
                      c.F32(model["norm_eps"]))


def _layer_program(mixer):
    """``h + mixer(stack, RMSNorm_i(h), model, j)``: sublayer ``i`` of the
    model, the ``j``-th of its kind's stack."""
    @functools.partial(jax.jit, static_argnums=(0,))
    def run(traced_with, norms, stack, i, j, h):
        model = dict(traced_with[0])
        return h + mixer(stack, _normed(norms, i, h, model), model, j)
    return run


PROGRAMS = {
    "conv": _layer_program(lambda st, v, model, j: short_conv(
        _at(st, j), v, model)),
    "attention": _layer_program(lambda st, v, model, j: attention(
        _at(st, j), v, model)),
    "mlp": _layer_program(lambda st, v, model, j: swiglu(
        v, *(_at(st, j)[k]["kernel"].astype(c.F32) for k in ("fc1", "fc2")))),
    "experts": _layer_program(experts),
}


def run_layers(params: Dict, x, model: Dict):
    """Layer ``l``: its mixer by ``layer_types[l]``, then its feed-forward,
    dense below :func:`dense_layers`; each the next of its kind's stack (an
    expert layer's place in ITS stack is counted from the published
    ``num_dense_layers``, whatever ``dense_layers`` is turned to)."""
    norms, mixers = params["layers"]["input_norm"], params["mixers"]
    traced_with = _traced_with(model)
    seen = {"conv": 0, "attention": 0}
    published = int(model["num_dense_layers"])
    for l, kind in enumerate(model["layer_types"]):
        mixer = "conv" if kind == "conv" else "attention"
        dense = l < dense_layers(model)
        places = ((mixer, seen[mixer]),
                  ("mlp", min(l, published - 1)) if dense
                  else ("experts", max(l - published, 0)))
        seen[mixer] += 1
        for half, (name, at) in enumerate(places):
            x = jax.block_until_ready(PROGRAMS[name](
                traced_with, norms, mixers[name], jnp.int32(2 * l + half),
                jnp.int32(at), x))
    return x


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][tokens].astype(c.F32)
        x = run_layers(params, x, model)
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          c.F32(model["norm_eps"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _tied(dtype, hidden, table):
    return hidden @ table.astype(dtype).T


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: tied."""
    with jax.default_matmul_precision("highest"):
        return _tied(c.F32, hidden, head_kernel(params))


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
