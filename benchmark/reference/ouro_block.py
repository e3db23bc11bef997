"""Plain reference of ByteDance's Ouro (a LoopLM; Ouro-2.6B): a dense
decoder whose whole stack of layers runs ``total_ut_steps`` times over the
SAME weights.  A layer is a sandwich: RMSNorm -> causal multi-head
attention with RoPE -> RMSNorm, added; RMSNorm -> SwiGLU MLP -> RMSNorm,
added.  After the last layer of every pass the one final RMSNorm, whose
output the next pass starts from, and on it the exit gate (a hidden -> 1
linear with a bias).  The exit distribution over the passes says from which
pass a token's logits are read; at the published ``early_exit_threshold``
of 1.0 that is the last.  Untied head.

float32, ``highest`` matmul precision, straightforward ``jax.numpy``, no
cache: every pass attends over the whole sequence, so "pass t's keys" are
simply the keys that pass computed.  What the published config leaves to
the family's modelling code is ONE function each below (``passes``,
``post_norms``, ``normed_between``, ``keys_pass``, ``exit_pdf``,
``exit_pass``), so that another reading changes one line and
``tools/serve_faults.py`` can plant a fault by patching one name.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference import common as c


# ---- the readings ----------------------------------------------------------

def passes(model: Dict) -> int:
    """How many times the stack runs: ``total_ut_steps``."""
    return int(model["total_ut_steps"])


def post_norms(model: Dict) -> bool:
    """Whether a sublayer's OUTPUT is normed too before it is added (four
    norms a layer), as the family's decoder layer does."""
    return True


def normed_between(model: Dict) -> bool:
    """Whether the final norm lies after EVERY pass, its output feeding the
    next pass (not after the last pass alone, feeding the head and the
    gates only)."""
    return True


def keys_pass(t: int, model: Dict) -> int:
    """Which pass's keys and values a query of pass ``t`` (from 0) sees at
    a layer: its own.  Nothing of another pass reaches it."""
    return t


def exit_pdf(lams: List) -> List:
    """The exit distribution from the passes' gate probabilities lambda_t:
    p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T, and the last pass
    takes what is left, p_T = prod_{j<T} (1 - lambda_j)."""
    out, stay = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        out.append(lam * stay)
        stay = stay * (1.0 - lam)
    return out + [stay]


def exit_pass(pdf: List, model: Dict):
    """The pass (from 0) a token's logits are read from: the first at
    which the distribution's cumulative mass reaches
    ``early_exit_threshold``, the last where none does.  At a threshold of
    1 or more that is the last whatever the gates say (in exact arithmetic
    the mass before the last pass stays under 1)."""
    last = len(pdf) - 1
    threshold = float(model["early_exit_threshold"])
    chosen = jnp.full(pdf[0].shape, last, jnp.int32)
    if threshold >= 1.0:
        return chosen
    cum = jnp.zeros_like(pdf[0])
    for t in reversed(range(last)):
        # walked from the back, so that the FIRST pass over the line wins
        cum_t = sum(pdf[:t + 1], cum)
        chosen = jnp.where(cum_t >= threshold, t, chosen)
    return chosen


# ---- the layer -------------------------------------------------------------

def rotated_qkv(layer: Dict, x, model: Dict):
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    h1 = c.rms_norm(x, layer["input_norm"]["scale"], eps)
    q, k, v = c.split_qkv(h1 @ layer["attention"]["qkv"]["kernel"], n, nkv, d)
    return c.rope(q, model["rope_theta"]), c.rope(k, model["rope_theta"]), v


def block(layer: Dict, x, model: Dict, seen=None):
    """One layer of one pass.  ``seen``: another pass's (k, v) of this layer
    for the queries to read (``keys_pass``), None for the pass's own.
    Returns the layer's output and its own (k, v)."""
    eps = model["rms_norm_eps"]
    q, k, v = rotated_qkv(layer, x, model)
    ctx = c.causal_attention(q, *(seen or (k, v)), None)
    a = ctx @ layer["attention"]["dense"]["kernel"]
    if post_norms(model):
        a = c.rms_norm(a, layer["attn_out_norm"]["scale"], eps)
    x = x + a
    u = c.rms_norm(x, layer["post_norm"]["scale"], eps)
    fc1 = layer["mlp"]["fc1"]["kernel"]            # [h, 2, ffn]: up, gate
    up, gate = u @ fc1[:, 0, :], u @ fc1[:, 1, :]
    m = (up * jax.nn.silu(gate)) @ layer["mlp"]["fc2"]["kernel"]
    if post_norms(model):
        m = c.rms_norm(m, layer["mlp_out_norm"]["scale"], eps)
    return x + m, (k, v)


# ---- the passes ------------------------------------------------------------

def run_passes(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> (the final norm's output after each pass
    [b, s, h], each pass's gate probability [b, s]), float32, a list entry
    a pass."""
    layers = params["layers"]
    depth = jax.tree.leaves(layers)[0].shape[0]
    total = passes(model)
    eps = model["rms_norm_eps"]
    final = params["final_norm"]["scale"].astype(c.F32)
    gate = c.f32(params["exit_gate"])
    # another pass's keys are kept only where some pass reads them
    wanted = {keys_pass(t, model) for t in range(total)
              if keys_pass(t, model) != t}
    with jax.default_matmul_precision("highest"):
        # a layer is indexed INSIDE the jitted step (no slice of the stacks
        # beside the engine's pool on the device), the stacks its ARGUMENT
        # (closed over, they would be constants of the lowered program)
        step = jax.jit(lambda stacks, i, h, seen: block(
            c.f32(jax.tree.map(lambda a: a[i], stacks)), h, model, seen))
        x = params["embedding"]["word_embeddings"].astype(c.F32)[tokens]
        kept: Dict[int, List] = {}
        outs, lams = [], []
        for t in range(total):
            source = keys_pass(t, model)
            mine = []
            for i in range(depth):
                seen = None if source == t else kept[source][i]
                x, kv = step(layers, i, x, seen)
                if t in wanted:
                    mine.append(kv)
            kept[t] = mine
            normed = c.rms_norm(x, final, eps)
            if normed_between(model):
                x = normed
            outs.append(normed)
            lams.append(jax.nn.sigmoid(
                (normed @ gate["kernel"])[..., 0] + gate["bias"][0]))
    return outs, lams


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> the final norm's output [b, s, h] float32 of
    the pass each token's logits are read from (``exit_pass``)."""
    outs, lams = run_passes(params, tokens, model)
    chosen = exit_pass(exit_pdf(lams), model)
    hidden = outs[-1]
    for t, out in enumerate(outs[:-1]):
        hidden = jnp.where((chosen == t)[..., None], out, hidden)
    return hidden


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., vocab] float32: an untied head."""
    return c.project(hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32."""
    return head(params, stack(params, tokens, model), model)
