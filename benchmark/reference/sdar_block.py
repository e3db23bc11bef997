"""Plain reference of the SDAR decoder (JetLM ``sdar_moe``; SDAR-30B-A3B-Chat)
and of its generation by diffusion over blocks.

The stack.  Layer ``l``: ``h = x + Attn_l(RMSNorm(x))``; ``x' = h +
MoE_l(RMSNorm(h))``; a final RMSNorm; an untied head.

    attention: q, k, v = u W_q, u W_k, u W_v (no bias); ONE RMSNorm over a
           head's 128 values on q and on k BEFORE the rotation; RoPE over
           all 128 dims; softmax at 128^-0.5; W_o.  The mask is BLOCK-causal:
           with block length B the query at position p sees every key at
           positions < (p // B + 1) * B, all earlier blocks and the WHOLE of
           its own, keys after it included.  Blocks are cut from position 0.
    experts:   s = softmax(h W_r) in float32 over all 128; top-8; weights the
           chosen s over their sum (``norm_topk_prob``); expert
           W_2 (SiLU(W_1 h) * W_3 h); no shared expert.

Generation (:func:`generate`, the family's published ``generate.py``).  The
sequence is ``prompt + masks``, ``mask_token_id`` a row of the vocabulary.
Block by block from the prompt's last block boundary: the block's input
holds the tokens known so far (the prompt's remainder in the first one) and
the mask id elsewhere; a DENOISING STEP is a forward, logits at all B
positions with NO shift (the logits at a masked position are over the token
AT it), ``x0 = argmax``, confidence ``softmax(logits)[x0]``, and some masked
positions take their ``x0`` (:func:`chosen`).  Here every step is a FULL
forward of ``prompt + blocks so far + masks`` with no K/V kept, so what the
published loop does with a cache (the prompt's whole blocks run once; a
finished block run once more with its final tokens, THAT pass's K/V being
what later blocks read) is what a full forward computes anyway: a finished
block's keys are those of its final tokens.  Greedy only.

``stack`` as ``benchmark/lib/check.py`` ``emitted_reference`` calls it.  That
caller hands ``stack`` the probe's ``prompt + tokens`` right-padded, reads
rows ``len(prompt) - 1 + i`` to score emitted token ``i``, knows no step
order and cannot be edited.  Under ``remasking_strategy`` ``sequential`` with
ONE token a step the order is the positions', so the state of every step
follows from the tokens alone: when the token at position ``p`` was
unmasked, the positions of its block left of it held their tokens and
``p`` and everything right of it in the block held the mask id.  ``stack``
therefore returns, at row ``q``, the final hidden state AT POSITION
``q + 1`` of the NOISED STREAM ``s = (q + 1) % B``: a forward in which every
position at offset ``>= s`` of ITS block holds the mask id, reading the keys
and values of all EARLIER blocks from the clean stream (the sequence as
given, block-causal) and of its own block from itself.  That is SDAR's
training-time two-stream view, ``1 + B`` streams a layer.
``tests/test_sdar.py`` holds it equal to :func:`generate`'s log-probabilities
under ``sequential``.  :func:`logits` is the clean stream alone: one
block-causal forward.

float32 at ``highest`` matmul precision, no cache, no kernel: every held
expert on every token under a mask, attention as a plain softmax over the
keys of the mask.  It imports nothing from the program.

Departures from the published modelling code, none of which changes a result:
it reads the *program's* parameter tree (``common.py``: group-major fused QKV;
RoPE on interleaved pairs, the checkpoints' rotate-half under a fixed
permutation of a head's dims that the head norms' scales take too; a GLU's
``fc1`` holds the value ``W_3`` at ``[..., 0, :]`` and the gated ``W_1`` at
``[..., 1, :]``); of the routed experts this chip HOLDS a share
(``model["first_held_expert"]`` and the stacks' own length say which), and
what the absent ones would add is left out, as in the program; the head's
matrix is padded to whole 128s as the program's is, the padding's columns
are part of the softmax's sum there and here, and no sample falls on them.

The readings the config leaves open, one function each so that another
reading changes one line (``tools/serve_faults.py`` and ``tests/test_sdar.py``
patch them): :func:`mask_end` (block-causal), :func:`own_block_stream` (a
block's keys come from its own stream), :func:`earlier_blocks_clean` (earlier
blocks' from the committed, clean one), :func:`logits_shift` (none),
:func:`qk_normed`, :func:`rope_position`, :func:`gates_normalised`,
:func:`chosen`.  The dtype is ``common.F32``, looked up at the call:
``benchmark/control.py`` patches that name for its bfloat16 control.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as c

EXPERT_BLOCK = 4
STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


# ---- the readings -----------------------------------------------------------

def block_length(model: Dict) -> int:
    return int(model["diffusion_block_length"])


def mask_end(pos, model: Dict):
    """One past the last key the query at ``pos`` sees: the end of its
    block (``pos + 1`` would be a causal model)."""
    b = block_length(model)
    return (pos // b + 1) * b


def own_block_stream(model: Dict) -> bool:
    """A noised stream's query reads the keys of ITS OWN block from its own
    stream (False: from the clean one, as if the block's final tokens were
    known before they are)."""
    return True


def earlier_blocks_clean(model: Dict) -> bool:
    """Earlier blocks' keys are those of their final tokens: the commit pass
    (False: of the stream with the mask id in the place of the whole block,
    what is left in the cache when the commit pass is left out)."""
    return True


def logits_shift(model: Dict) -> int:
    """The logits at a position are over the token AT it: 0 (1: over the
    next one's, a causal model's reading)."""
    return 0


def qk_normed(model: Dict) -> bool:
    """One RMSNorm a head on q and on k, before RoPE (the base family's)."""
    return True


def rope_position(pos, model: Dict):
    """A row is rotated at its own position (not at its mask position)."""
    return pos


def gates_normalised(model: Dict) -> bool:
    return bool(model.get("norm_topk_prob", True))


def chosen(masked, conf, strategy: str, per_step: int, threshold: float):
    """Which masked positions of a block take their sample this step.
    ``sequential``: the leftmost ``per_step``; ``low_confidence_static``:
    the ``per_step`` most confident (ties: the leftmost);
    ``low_confidence_dynamic``: every one whose confidence passes
    ``threshold``, and at least those ``per_step``.  numpy, ``[B]``."""
    where = np.flatnonzero(masked)
    if strategy == "sequential":
        take = where[:per_step]
    else:
        order = where[np.argsort(-conf[where], kind="stable")]
        take = order[:per_step]
        if strategy == "low_confidence_dynamic":
            take = np.union1d(take, where[conf[where] > threshold])
    out = np.zeros_like(masked)
    out[take] = True
    return out


CHOICES = ("mask_end", "own_block_stream", "earlier_blocks_clean",
           "logits_shift", "qk_normed", "rope_position", "gates_normalised")
SIZES = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "hidden_size", "rms_norm_eps", "num_experts_per_tok",
         "norm_topk_prob", "first_held_expert", "rope_theta",
         "diffusion_block_length", "mask_token_id")


def _traced_with(model: Dict):
    """Hashable: everything a layer program's trace reads that is not an
    operand."""
    return (tuple((k, model[k]) for k in SIZES if k in model), c.F32,
            tuple(globals()[name] for name in CHOICES))


# ---- the sublayers ----------------------------------------------------------

def rope_at(x, pos, theta: float):
    """x [streams, s, heads, d] rotated at ``pos`` [s]; interleaved pairs."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=c.F32) / d))
    ang = pos.astype(c.F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xp = x.reshape(*x.shape[:-1], d // 2, 2)
    even, odd = xp[..., 0], xp[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(att: Dict, v, model: Dict):
    """``v`` [streams, s, h], stream 0 the CLEAN one (the tokens as given),
    stream ``1 + j`` the noised one whose blocks hold the mask id from
    offset ``j`` on.  A query of any stream reads the keys of EARLIER blocks
    from the clean stream and of its own block from its own stream; for the
    clean stream that is the plain block-causal mask."""
    n, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n
    g, blk = n // nkv, block_length(model)
    att = c.f32(att)
    streams, s, _ = v.shape
    q, k, val = c.split_qkv(v @ att["qkv"]["kernel"], n, nkv, d)
    if qk_normed(model):
        eps = c.F32(model["rms_norm_eps"])
        q = c.rms_norm(q, att["q_norm"]["scale"], eps)
        k = c.rms_norm(k, att["k_norm"]["scale"], eps)
    pos = jnp.arange(s)
    theta = float(model["rope_theta"])
    q = rope_at(q, rope_position(pos, model), theta)
    k = rope_at(k, rope_position(pos, model), theta)
    end = mask_end(pos, model)                       # [s] one past the last
    first = jnp.minimum(pos // blk * blk, end)       # the own block's first
    kpos = pos[None, :]
    # keys of earlier blocks: the clean stream's (or, the commit pass left
    # out, those of the stream that holds the mask id in the whole block)
    early = (kpos < first[:, None])
    own = (kpos >= first[:, None]) & (kpos < end[:, None])

    def one_stream(args):
        qs, ks, vs = args
        k_early, v_early = ((k[0], val[0]) if earlier_blocks_clean(model)
                            else (k[1 % streams], val[1 % streams]))
        k_own, v_own = (ks, vs) if own_block_stream(model) else (k[0], val[0])
        qg = qs.reshape(s, nkv, g, d)
        sc_e = jnp.einsum("qkgd,skd->kgqs", qg, k_early) / jnp.sqrt(c.F32(d))
        sc_o = jnp.einsum("qkgd,skd->kgqs", qg, k_own) / jnp.sqrt(c.F32(d))
        scores = jnp.concatenate([
            jnp.where(early[None, None], sc_e, -jnp.inf),
            jnp.where(own[None, None], sc_o, -jnp.inf)], axis=-1)
        p = jax.nn.softmax(scores, axis=-1)
        out = (jnp.einsum("kgqs,skd->qkgd", p[..., :s], v_early)
               + jnp.einsum("kgqs,skd->qkgd", p[..., s:], v_own))
        return out.reshape(s, n * d)

    ctx = jax.lax.map(one_stream, (q, k, val))
    return ctx @ att["dense"]["kernel"]


def router_weights(router: Dict, x, model: Dict):
    """[tokens, experts]: the weight of each of the router's experts for each
    token, zero for the ones it did not choose; normalised over ALL the
    chosen, held here or not."""
    k = model["num_experts_per_tok"]
    s = jax.nn.softmax(x @ router["kernel"], axis=-1)
    w, picked = jax.lax.top_k(s, k)
    if gates_normalised(model):
        w = w / w.sum(-1, keepdims=True)
    dense = jnp.zeros_like(s)
    return dense.at[jnp.arange(s.shape[0])[:, None], picked].set(w)


def experts(moe: Dict, v, model: Dict):
    """The held experts' part of sum_i w_i E_i(v); ``moe`` one layer's
    router and expert stacks in the weights' dtype, ``EXPERT_BLOCK`` experts
    cast at a time."""
    lead, d = v.shape[:-1], v.shape[-1]
    x = v.reshape(-1, d)
    w = router_weights(c.f32(moe["router"]), x, model)
    fc1, fc2 = (moe["experts"][k]["kernel"] for k in ("fc1", "fc2"))
    held = fc1.shape[0]
    first = int(model.get("first_held_expert", 0))
    w = w[:, first:first + held]
    size = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else held

    def one_block(acc, i):
        f1 = jax.lax.dynamic_slice_in_dim(fc1, i * size, size).astype(c.F32)
        f2 = jax.lax.dynamic_slice_in_dim(fc2, i * size, size).astype(c.F32)
        wb = jax.lax.dynamic_slice_in_dim(w, i * size, size, axis=1).T
        up = jnp.einsum("th,ehf->etf", x, f1[:, 0])
        gate = jnp.einsum("th,ehf->etf", x, f1[:, 1])
        y = jnp.einsum("etf,efh->eth", up * jax.nn.silu(gate), f2)
        return acc + (wb[:, :, None] * y).sum(0), None

    out, _ = jax.lax.scan(one_block, jnp.zeros_like(x),
                          jnp.arange(held // size))
    return out.reshape(*lead, d)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(traced_with, layers, i, x):
    """Layer ``i`` (a traced index into the stacks) on ``x`` [streams, s,
    h]: jitted ONCE here (a ``jax.jit`` made anew at every call is a new
    program to jax every time)."""
    model = dict(traced_with[0])
    layer = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        layers)
    eps = c.F32(model["rms_norm_eps"])
    h = x + attention(layer["attention"], c.rms_norm(
        x, layer["input_norm"]["scale"].astype(c.F32), eps), model)
    return h + experts(layer["moe"], c.rms_norm(
        h, layer["post_norm"]["scale"].astype(c.F32), eps), model)


def _streams(params: Dict, tokens, model: Dict, noised: bool):
    """The final norm's output of the clean stream of ``tokens`` [s] alone
    (``[1, s, h]``) or with the ``B`` noised streams behind it."""
    blk, mask_id = block_length(model), int(model["mask_token_id"])
    rows = [tokens]
    if noised:
        off = jnp.arange(tokens.shape[0]) % blk
        rows += [jnp.where(off >= j, mask_id, tokens) for j in range(blk)]
    with jax.default_matmul_precision("highest"):
        x = params["embedding"]["word_embeddings"][jnp.stack(rows)].astype(
            c.F32)
        layers = params["layers"]
        traced_with = _traced_with(model)
        for i in range(jax.tree.leaves(layers)[0].shape[0]):
            x = jax.block_until_ready(
                _layer(traced_with, layers, jnp.int32(i), x))
        return c.rms_norm(x, params["final_norm"]["scale"].astype(c.F32),
                          c.F32(model["rms_norm_eps"]))


def stack(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> [b, s, h] float32: row ``q`` the final hidden
    state AT POSITION ``q + 1 - shift`` of the noised stream ``(q + 1) % B``
    (the module's docstring); the last row has no such position and holds
    the clean stream's."""
    blk, shift = block_length(model), logits_shift(model)
    out = []
    s = tokens.shape[1]
    # whole blocks, as the published loop lays the sequence out: the last
    # block's positions past the row hold the mask id in every noised stream
    tokens = jnp.pad(tokens, ((0, 0), (0, -s % blk)))
    for row in tokens:
        x = _streams(params, row, model, noised=True)        # [1 + B, s', h]
        at = jnp.minimum(jnp.arange(s) + 1, s - 1)
        stream = 1 + at % blk
        out.append(x[stream, jnp.maximum(at - shift, 0)])
    return jnp.stack(out)


@functools.partial(jax.jit, static_argnums=(0,))
def _project(dtype, hidden, kernel):
    return hidden @ kernel.astype(dtype)


def head(params: Dict, hidden, model: Dict):
    """hidden [..., h] -> logits [..., padded vocab] float32: untied."""
    with jax.default_matmul_precision("highest"):
        return _project(c.F32, hidden, params["lm_head"]["kernel"])


def logits(params: Dict, tokens, model: Dict):
    """tokens [b, s] int32 -> logits [b, s, vocab] float32: ONE block-causal
    forward of the sequence as given; row ``p`` is over the token AT ``p``."""
    hidden = jnp.concatenate(
        [_streams(params, row, model, noised=False) for row in tokens])
    return head(params, hidden, model)


def generate(params: Dict, prompt: List[int], n: int, model: Dict,
             strategy: str = "low_confidence_dynamic", steps: int = 4,
             threshold: float = 0.9) -> Tuple[List[int], List[int], List[float]]:
    """The published loop, greedy, with a FULL forward at every step.
    Returns the ``n`` tokens after ``prompt``, the step (counted over the
    whole generation) that unmasked each, and its log-probability at that
    step.  Whole blocks are denoised, as published, and the tokens past ``n``
    dropped."""
    assert strategy in STRATEGIES and n >= 1
    blk, mask_id = block_length(model), int(model["mask_token_id"])
    vocab = int(model["vocab_size"])
    per_step = max(1, blk // steps)
    start = len(prompt) // blk * blk
    total = -(-(len(prompt) + n) // blk) * blk
    seq = np.full((total,), mask_id, np.int32)
    seq[:len(prompt)] = prompt
    known = np.arange(total) < len(prompt)
    step_of = np.zeros((total,), np.int64)
    logp_of = np.zeros((total,), np.float64)
    step = 0
    for first in range(start, total, blk):
        own = slice(first, first + blk)
        while not known[own].all():
            feed = np.where(known, seq, mask_id)[None]
            lg = np.asarray(logits(params, jnp.asarray(feed), model))[0, own]
            logp = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
            x0 = lg[:, :vocab].argmax(-1)
            lp = logp[np.arange(blk), x0]
            take = chosen(~known[own], np.exp(lp), strategy, per_step,
                          threshold)
            at = first + np.flatnonzero(take)
            seq[at], known[at] = x0[take], True
            step_of[at], logp_of[at] = step, lp[take]
            step += 1
    out = slice(len(prompt), len(prompt) + n)
    return (seq[out].tolist(), step_of[out].tolist(), logp_of[out].tolist())
