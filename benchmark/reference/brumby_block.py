"""Plain reference of the Brumby decoder (manifestai Brumby-14B-Base,
``brumby``): the Qwen3 block whose every layer replaces softmax attention
by gated degree-2 POWER RETENTION.  Layer input ``x``, query head ``h``,
KV head ``c`` (its group's):

    u     = RMSNorm(x)
    q^h   = RoPE(RMSNorm_d(u W_q^h)),  k^c = RoPE(RMSNorm_d(u W_k^c)),  v^c = u W_v^c
    l_t^c = log sigmoid(u_t . w_g^c + b_g^c)  <= 0,   L_t^c = sum_{s <= t} l_s^c
    a_tj  = exp(L_t - L_j) (q_t . k_j)^degree          j <= t  (j = t: decay 1)
    y_t   = sum_j a_tj v_j / (sum_j a_tj + eps)
    x'    = x + [y^1 .. y^n] W_o;    x'' = x' + SwiGLU(RMSNorm(x'))

This is the ATTENTION form: the ``t x t`` weights with their decay, a block
of queries at a time past 512 positions (``common.in_query_blocks``).  It
never builds the recurrent state the program serves from, so the two share
no algorithm: what they agree on is the function.  float32, ``highest``
matmul precision, no kernels, no cache.

It reads the *program's* parameter tree (``common.py``: group-major fused
QKV, RoPE on interleaved pairs; the head norms are ``attention/q_norm`` and
``attention/k_norm``, one scale of ``head_dim`` shared by the heads, the
gate ``attention/gate`` with its bias).  Its dtype is ``common.F32`` and its
mask ``common.causal_mask``, both looked up at the call: ``benchmark/
control.py`` patches those two names for its bfloat16 control and for its
forgetful reference.

The choices a planted fault turns (``tools/serve_faults.py`` patches
them): :func:`log_decay` (the gate), :func:`degree`, :func:`normalised`.

Memory.  One layer at a time, and of a layer one matrix at a time: the
attention's four matrices (0.25 GB in float32), then the MLP's up, gate and
down (0.36 GB each) cast as they are used; the head in blocks of
``HEAD_BLOCK`` vocabulary rows (0.34 GB a block, where the whole float32
matrix is 3.1 GB).  About 1 GB beside the weights and the state pool.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as c

HEAD_BLOCK = 16384     # vocabulary rows the head multiplies at a time


def log_decay(gate, u):
    """l [b, s, nkv] from the gate's weights and the layer's normed input."""
    return jax.nn.log_sigmoid(u @ gate["kernel"].astype(c.F32)
                              + gate["bias"].astype(c.F32))


def degree(model: Dict) -> int:
    """The power of the query-key product."""
    return int(model["assumed"]["degree"])


def normalised(model: Dict) -> bool:
    """Whether a row's output is divided by the sum of its weights."""
    return True


def retention(q, k, v, big_l, model: Dict):
    """q [b,s,n,d]; k, v [b,s,nkv,d]; big_l [b,s,nkv] the running sum of the
    log-decays.  The attention form, a block of queries at a time."""
    b, s, n, d = q.shape
    nkv = k.shape[2]
    g = n // nkv
    p = degree(model)
    eps = c.F32(model["assumed"]["eps"])
    lk = big_l.transpose(0, 2, 1)                              # [b, nkv, s]

    def attend(qb, start):
        size = qb.shape[1]
        qg = qb.reshape(b, size, nkv, g, d)
        qk = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)
        at = jnp.minimum(start + jnp.arange(size), s - 1)      # padded: last
        gap = lk[:, :, at][:, :, None, :, None] - lk[:, :, None, None, :]
        ok = c.causal_mask(start, size, s, None)[None, None, None]
        w = jnp.where(ok, jnp.exp(jnp.where(ok, gap, 0.0)) * qk ** p, 0.0)
        num = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
        if normalised(model):
            num = num / (w.sum(-1).transpose(0, 3, 1, 2)[..., None] + eps)
        return num.reshape(b, size, n * d)

    size = c.query_block(s, n)
    return attend(q, 0) if size >= s else c.in_query_blocks(attend, q, size)


def attention(p: Dict, x, model: Dict):
    """x + the retention sublayer; ``p`` the layer's norm and attention
    leaves, cast here."""
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    att = c.f32(p["attention"])
    u = c.rms_norm(x, p["input_norm"]["scale"].astype(c.F32), eps)
    q, k, v = c.split_qkv(u @ att["qkv"]["kernel"], n, nkv, d)
    q = c.rms_norm(q, att["q_norm"]["scale"], eps)
    k = c.rms_norm(k, att["k_norm"]["scale"], eps)
    q, k = c.rope(q, model["rope_theta"]), c.rope(k, model["rope_theta"])
    big_l = jnp.cumsum(log_decay(p["attention"]["gate"], u), axis=1)
    return x + retention(q, k, v, big_l, model) @ att["dense"]["kernel"]


def mlp(p: Dict, x, model: Dict):
    """x + SwiGLU(RMSNorm(x)), a matrix cast at a time."""
    h = c.rms_norm(x, p["post_norm"]["scale"].astype(c.F32),
                   model["rms_norm_eps"])
    fc1 = p["mlp"]["fc1"]["kernel"]                # [h, 2, ffn]: up, gate
    act = (h @ fc1[:, 0, :].astype(c.F32)) * jax.nn.silu(
        h @ fc1[:, 1, :].astype(c.F32))
    return x + act @ p["mlp"]["fc2"]["kernel"].astype(c.F32)


def run_layers(params: Dict, x, model: Dict):
    layers = params["layers"]
    depth = jax.tree.leaves(layers)[0].shape[0]
    att = jax.jit(lambda p, h: attention(p, h, model))
    ffn = jax.jit(lambda p, h: mlp(p, h, model))
    for i in range(depth):
        layer = jax.tree.map(lambda a: a[i], layers)
        x = jax.block_until_ready(att(
            {k: layer[k] for k in ("input_norm", "attention")}, x))
        x = jax.block_until_ready(ffn(
            {k: layer[k] for k in ("post_norm", "mlp")}, x))
    return x


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][tokens].astype(c.F32)
        x = run_layers(params, x, model)
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          model["rms_norm_eps"])


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: untied, a block of
    vocabulary rows at a time."""
    kernel = params["lm_head"]["kernel"]           # [h, vocab]
    vocab = kernel.shape[1]
    return jnp.concatenate(
        [c.project(hidden, kernel[:, i:i + HEAD_BLOCK])
         for i in range(0, vocab, HEAD_BLOCK)], axis=-1)


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
