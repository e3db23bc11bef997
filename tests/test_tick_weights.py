"""The serving tick reads a dense layer's weights where they lie (PR 42):
``transformer_forward`` under ``paged`` keeps the dense projections' stacks
outside the layer scan and ``_linear`` reads layer ``l`` of them
(``StackedLinear``).  At tiny widths on the CPU, over the four kinds of
stack the rule meets: the tick's log-probs agree with the SCANNED training
forward on the same weights, with and without the rule; the engine holds
the caller's own arrays and no other buffer the size of a weight; and the
trainer's forward still scans every leaf.  The pair kernel for a GLU
``fc1`` (``ops/pallas/stacked_linear.py``) in interpret mode against the
plain product."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models import transformer
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.ops.pallas import stacked_linear
from megatron_llm_tpu.ops.quant import quantize_layer_weights_int8

from parity import DENSE_ATOL, LOGPROB_ATOL

VOCAB = 256
NEVER = 10 ** 9
COMMON = dict(vocab_size=VOCAB, params_dtype="float32", use_flash_attn=False,
              max_position_embeddings=512, seq_length=256)

# name -> (family, widths, quantize the layers' linears to int8)
STACKS = {
    # a parallel block (one norm, attention and MLP side by side), MQA,
    # a plain fc1, the head tied to the embedding
    "falcon_parallel_tied": ("falcon", dict(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=1, tie_embed_logits=True), False),
    # power retention on a state pool, GLU fc1, untied head
    "brumby_retention": ("brumby", dict(
        num_layers=2, hidden_size=64, num_attention_heads=8,
        num_attention_heads_kv=2, kv_channels=16, ffn_hidden_size=96),
        False),
    # a period of four (three window layers to a full one, unrolled in
    # the scan's body), routed experts beside four shared ones (GLU)
    "commanda_pattern_shared": ("commanda", dict(
        num_layers=4, hidden_size=64, num_attention_heads=8,
        num_attention_heads_kv=2, kv_channels=16, num_experts=16,
        moe_router_topk=4, moe_ffn_hidden_size=32, ffn_hidden_size=32,
        sliding_window_size=32, moe_capacity_factor=8.0,
        init_method_std=0.3), False),
    # every linear of the stack a {kernel_q, kernel_scale} pair
    "llama_int8_weights": ("llama2", dict(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2), True),
}


def build(name):
    family, widths, int8 = STACKS[name]
    cfg = make_config(family, **{**COMMON, **widths})
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    if int8:
        params = quantize_layer_weights_int8(params)
    return cfg, params


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, VOCAB, n)] for n in lengths]


def serve(cfg, params):
    """Two greedy requests of unequal length through the engine: prompt
    rows in chunks, then decode rows, in the same ticks."""
    gen.clear_jit_cache()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, page_size=8,
                                   max_seq=256, prefill_chunk=16)
    reqs = [eng.submit(p, 10, top_k=1, termination_id=NEVER)
            for p in prompts(37, 21)]
    eng.run_until_idle()
    return eng, [r.result(timeout=120) for r in reqs], reqs


def scanned_log_probs(cfg, params, tokens):
    """The trainer's forward (no cache, every layer scanned) on one
    sequence: the log-prob of each token after the first."""
    ids = jnp.asarray([tokens], jnp.int32)
    logits, _ = model_forward(cfg, params, ids)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(jnp.take_along_axis(
        logp[0, :-1], ids[0, 1:, None], axis=-1)[:, 0])


def weight_shapes(params):
    """(shape, dtype) of every dense weight leaf, and of a layer's slice
    of it."""
    out = set()
    for leaf in jax.tree.leaves(params):
        if leaf.ndim >= 2:
            out.add((leaf.shape, leaf.dtype))
            out.add((leaf.shape[1:], leaf.dtype))
    return out


@pytest.mark.parametrize("name", list(STACKS))
def test_tick_agrees_with_the_scanned_forward_and_copies_no_weight(
        name, monkeypatch):
    cfg, params = build(name)
    own = {id(leaf) for leaf in jax.tree.leaves(params)}
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}

    eng, results, reqs = serve(cfg, params)
    # the rule was taken: the tick's layers were handed stacks
    linears, rest = transformer._take_linears(params["layers"])
    assert linears and "attention" in linears
    assert not any(k in rest.get("attention", {})
                   for k in ("qkv", "dense"))
    for (tokens, lps), req in zip(results, reqs):
        want = scanned_log_probs(cfg, params, tokens)[len(req.prompt) - 1:]
        np.testing.assert_allclose(np.asarray(lps), want, rtol=0,
                                   atol=DENSE_ATOL)

    # the caller's tree and leaves, untouched; and nothing else the size
    # of a weight (or of a layer's slice of one) came to live with it
    assert eng.params is params
    assert {id(leaf) for leaf in jax.tree.leaves(eng.params)} == own
    gc.collect()
    sizes = weight_shapes(params)
    pools = {id(a) for a in jax.tree.leaves(
        (eng.pool.kv, eng.wpool.kv if eng.wpool is not None else ()))}
    new = [a for a in jax.live_arrays()
           if id(a) not in before and id(a) not in own
           and id(a) not in pools and (a.shape, a.dtype) in sizes]
    assert not new, [(a.shape, a.dtype) for a in new]

    # the same requests with every leaf riding the scan, as before PR 42
    monkeypatch.setattr(transformer, "_take_linears", lambda t: ({}, t))
    _, scanned, _ = serve(cfg, params)
    gen.clear_jit_cache()
    for (tokens, lps), (tokens_s, lps_s) in zip(results, scanned):
        assert tokens == tokens_s
        np.testing.assert_allclose(np.asarray(lps), np.asarray(lps_s),
                                   rtol=0, atol=LOGPROB_ATOL)


@pytest.mark.parametrize("name", list(STACKS))
def test_the_trainer_still_scans_every_dense_leaf(name):
    """Without ``paged`` nothing is closed over: the jaxpr of the train
    forward holds ONE scan at its top level, and every stacked leaf is
    among its scanned operands (a patterned stack's as whole periods)."""
    cfg, params = build(name)
    tokens = jnp.zeros((1, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p: model_forward(cfg, p, tokens)[0])(params).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    (scan,) = scans
    n_xs = len(scan.invars) - scan.params["num_consts"] - scan.params[
        "num_carry"]
    xs = [tuple(v.aval.shape) for v in scan.invars[-n_xs:]]
    period = cfg.model.layer_period
    for leaf in jax.tree.leaves(params["layers"]):
        depth, rest = leaf.shape[0], tuple(leaf.shape[1:])
        as_scanned = ((depth,) + rest if period == 1
                      else (depth // period, period) + rest)
        assert as_scanned in xs, (leaf.shape, xs)
    # and no operand the scan closes over is the size of a stacked leaf
    consts = [tuple(v.aval.shape)
              for v in scan.invars[:scan.params["num_consts"]]]
    stacked = {tuple(leaf.shape) for leaf in jax.tree.leaves(params["layers"])
               if leaf.ndim >= 3}
    assert not stacked & set(consts)


@pytest.mark.parametrize("rows,h,ffn,layers", [
    (40, 256, 384, 3),       # rows padded to whole packed sublanes
    (16, 1280, 2176, 2),     # several blocks of h; ffn = 17 x 128
    (600, 128, 128, 1),      # more rows than one block holds
])
def test_pair_kernel_reads_the_stack_in_place(rows, h, ffn, layers):
    """``glu_stack_matmul`` (interpret mode) against the plain product of
    the same bfloat16 numbers, float32 accumulation on both sides; a layer
    other than the first, so that the block index is what picks it."""
    kx, kw = jax.random.split(jax.random.PRNGKey(rows))
    x = jax.random.normal(kx, (rows, h), jnp.bfloat16)
    stack = (0.05 * jax.random.normal(kw, (layers, h, 2, ffn))).astype(
        jnp.bfloat16)
    layer = layers - 1
    assert stacked_linear.refusal(x, stack) is None
    got = stacked_linear.glu_stack_matmul(x, stack, jnp.int32(layer),
                                          interpret=True)
    want = jnp.einsum("rh,hcf->rcf", x.astype(jnp.float32),
                      stack[layer].astype(jnp.float32))
    assert got.shape == (rows, 2, ffn) and got.dtype == jnp.bfloat16
    # one bfloat16 rounding of the result: 2^-8 of its size
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2 ** -7,
        atol=2 ** -7 * float(jnp.abs(want).max()) / 8)


@pytest.mark.parametrize("x_dtype,w_dtype,shape,says", [
    (jnp.float32, jnp.float32, (2, 128, 2, 128), "bfloat16 halves"),
    (jnp.bfloat16, jnp.int8, (2, 128, 2, 128), "bfloat16 halves"),
    (jnp.bfloat16, jnp.bfloat16, (2, 128, 256), "not [L, h, 2, ffn]"),
    (jnp.bfloat16, jnp.bfloat16, (2, 96, 2, 128), "128-lane groups"),
    (jnp.bfloat16, jnp.bfloat16, (2, 128, 2, 4544), "128-lane groups"),
])
def test_pair_kernel_refuses_in_a_sentence(x_dtype, w_dtype, shape, says):
    x = jax.ShapeDtypeStruct((8, shape[1]), x_dtype)
    stack = jax.ShapeDtypeStruct(shape, w_dtype)
    assert says in stacked_linear.refusal(x, stack)
