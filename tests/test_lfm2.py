"""LiquidAI LFM2 (``lfm2``: LFM2-24B-A2B) on the normal serving path, at tiny
widths on the CPU: a stack of mixer-then-feed-forward layers written as TWO
letters of ``sublayer_pattern`` a layer, gated short convolutions on a state
class of conv TAILS ALONE beside QK-normed, rotated GQA layers on K/V pages,
two dense SwiGLU layers and then bias-routed SwiGLU experts over a held
share, in a 10-layer order that ends in a part period.  Everything is
compared with the plain reference (``benchmark/reference/lfm2_block.py``) on
the same weights, logits and not tokens."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import lfm2_block as ref
from megatron_llm_tpu.config.arguments import MODEL_SIZES, lfm2_sublayers
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation.pools import (
    KEEPS,
    NOT_CARRIED,
    PagedKVPool,
    StatePool,
    memory_kind,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models import moe as moe_mod
from megatron_llm_tpu.models import sublayers
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.models.transformer import (
    LayerKind,
    layer_kinds,
    pool_classes,
)
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.ops import gated_delta as gd
from tests.parity import assert_memory, assert_memory_idle, held_pages

# float32 rounding: the program's fused QKV and grouped expert GEMMs sum in
# another order than the reference's plain products, at log-probs of
# magnitude ~5 (largest seen 5e-7 dense, 5e-7 through the engine)
ATOL = 2e-5
VOCAB = 256
NEVER = 10 ** 9
PAGE = 8
TYPES = "cc*ccc*ccc"            # cc*c, cc*c and a part period: cc
PATTERN = lfm2_sublayers(TYPES, 2)

WIDTHS = dict(
    sublayer_pattern=PATTERN, hidden_size=64, num_attention_heads=4,
    num_attention_heads_kv=2, kv_channels=16, ffn_hidden_size=96,
    num_experts=8, moe_router_topk=2, moe_ffn_hidden_size=32,
    vocab_size=VOCAB, max_position_embeddings=512, seq_length=256,
    params_dtype="float32", use_flash_attn=False)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(
    layer_types=["conv" if t == "c" else "full_attention" for t in TYPES],
    num_dense_layers=2, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, conv_L_cache=3, norm_eps=1e-5,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})


def lfm2_cfg(**kw):
    return make_config("lfm2", **{**WIDTHS, **kw})


def _drawn_norms(params, seed=3):
    """The initialiser leaves every norm's scale at 1; draw them, so that a
    norm the program forgot or misplaced would show."""
    key = jax.random.PRNGKey(seed)
    out = jax.tree.map(lambda a: a, params)
    att = out["mixers"]["attention"]
    for i, node in enumerate((out["layers"]["input_norm"], out["final_norm"],
                              att["q_norm"], att["k_norm"])):
        node["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), node["scale"].shape)
    return out


@pytest.fixture(scope="module")
def model():
    cfg = lfm2_cfg()
    return cfg, _drawn_norms(init_model_params(cfg, jax.random.PRNGKey(0)))


def reference_log_probs(params, tokens, model=MODEL):
    tokens = jnp.asarray([tokens], jnp.int32)
    logits = ref.logits(params, tokens, model)
    return np.asarray(ref_common.token_log_probs(logits, tokens))[0]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, VOCAB, n)] for n in lengths]


def engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, **{**dict(max_slots=4, page_size=PAGE, max_seq=256,
                               prefill_chunk=16), **kw})


def check(req, params, model=MODEL, atol=ATOL):
    tokens, lps = req.result(timeout=120)
    want = reference_log_probs(params, tokens, model)[len(req.prompt) - 1:]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=atol)


# ---- the family ------------------------------------------------------------

def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert m.short_conv and not m.mamba and not m.delta and not m.mla
    assert PATTERN == "CDCD*ECECECE*ECECECE"
    assert m.num_layers == m.depth == m.layer_period == 2 * len(TYPES)
    assert m.position_embedding_type == "rotary" and m.tie_embed_logits
    assert m.qk_head_norm and m.glu_activation == "swiglu"
    assert m.moe_gate_eps == 1e-6 and m.short_conv_kernel == 3
    assert layer_kinds(cfg) == tuple(
        LayerKind(None, c == "*", sublayers.SUBLAYERS[c]) for c in PATTERN)
    # a layer's two norms are two sublayers' ONE each, in order
    assert set(params["layers"]) == {"input_norm"} and "lm_head" not in params
    assert params["layers"]["input_norm"]["scale"].shape == (20, 64)
    conv, att, mlp, exp = (params["mixers"][k] for k in (
        "conv", "attention", "mlp", "experts"))
    assert set(conv) == {"in_proj", "conv", "dense"}     # no bias, no ``s``
    assert conv["in_proj"]["kernel"].shape == (8, 64, 3 * 64)   # B | C | u
    assert conv["conv"]["kernel"].shape == (8, 3, 64)
    assert conv["dense"]["kernel"].shape == (8, 64, 64)
    assert att["qkv"]["kernel"].shape == (2, 64, (4 + 2 * 2) * 16)
    assert att["q_norm"]["scale"].shape == att["k_norm"]["scale"].shape == (
        2, 16)
    assert mlp["fc1"]["kernel"].shape == (2, 64, 2, 96)
    assert exp["router"]["kernel"].shape == (8, 64, 8)
    assert exp["router"]["bias"].shape == (8, 8) and "shared" not in exp
    assert exp["experts"]["fc1"]["kernel"].shape == (8, 8, 2, 64, 32)
    assert float(jnp.abs(exp["router"]["bias"]).max()) > 0


def test_the_published_order_is_30_conv_10_attention_2_dense_38_experts():
    pattern = MODEL_SIZES["lfm2-24b-a2b"]["sublayer_pattern"]
    assert pattern == lfm2_sublayers("cc*c" * 10, 2) and len(pattern) == 80
    assert [pattern.count(c) for c in "C*DE"] == [30, 10, 2, 38]
    assert [i // 2 for i, c in enumerate(pattern) if c == "*"] == list(
        range(2, 40, 4))
    assert pattern[:4] == "CDCD" and "D" not in pattern[4:]
    # three scans, fourteen sublayer bodies (seven layers) for forty layers
    units = sublayers.stretches(pattern)
    assert units == (("CD", 2), ("*ECE", 1), ("CECE*ECE", 9))
    assert "".join(u * c for u, c in units) == pattern
    # the tests' ten layers end in a part period and still scan
    assert sublayers.stretches(PATTERN) == (
        ("CD", 2), ("*ECECECE", 2))
    cfg = make_config("lfm2-24b-a2b")
    m = cfg.model
    assert (m.hidden_size, m.num_attention_heads, m.num_attention_heads_kv,
            m.kv_channels, m.ffn_hidden_size, m.moe_ffn_hidden_size,
            m.num_experts, m.moe_router_topk, m.vocab_size) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, 65536)
    assert m.rope_theta == 1e6 and m.layernorm_epsilon == 1e-5
    assert m.moe_routed_scaling_factor == 1.0


REFUSALS = [
    (dict(sublayer_pattern="CEC"), "two letters a layer"),
    (dict(sublayer_pattern="DC*E"), "two letters a layer"),
    (dict(sublayer_pattern="*EEC"), "two letters a layer"),
    (dict(qk_head_norm=False), "norms q and k a head"),
    (dict(position_embedding_type="none"), "norms q and k a head"),
    (dict(glu_activation="geglu"), "RMSNorm and SwiGLU"),
    (dict(moe_selection_bias=False), "bias-corrected sigmoid scores"),
    (dict(moe_shared_experts=1), "no shared expert"),
    (dict(tie_embed_logits=False), "ties its head to the embedding"),
]


@pytest.mark.parametrize("kw,sentence", REFUSALS,
                         ids=[next(iter(kw)) + str(i)
                              for i, (kw, _) in enumerate(REFUSALS)])
def test_validate_family_refuses_in_a_sentence(kw, sentence):
    with pytest.raises(ValueError, match=sentence):
        lfm2_cfg(**kw)


def test_finalize_refuses_what_the_stack_does_not_build():
    with pytest.raises(AssertionError, match="a letter a layer, M "):
        lfm2_cfg(sublayer_pattern="CxCE")
    with pytest.raises(AssertionError, match="Mamba-2 states or conv tails"):
        make_config("nemotron_h", **{**WIDTHS, "sublayer_pattern": "MC",
                                     "num_experts": None, "mamba_num_heads": 8,
                                     "mamba_head_dim": 8, "ssm_state_size": 16})
    with pytest.raises(AssertionError, match="qk_head_norm is the 'mha'"):
        make_config("brumby", num_layers=2, hidden_size=64,
                    num_attention_heads=8, num_attention_heads_kv=2,
                    kv_channels=16, ffn_hidden_size=96, vocab_size=VOCAB,
                    qk_head_norm=True)
    with pytest.raises(ValueError, match="its letters M, E and \\*"):
        make_config("nemotron_h", **{**WIDTHS, "sublayer_pattern": "CE",
                                     "position_embedding_type": "none"})


# ---- the mixer --------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_ticks_conv_is_the_dense_one_bit_for_bit(dtype):
    """``conv_tick`` over runs that start, go on and cross ticks against
    ``causal_conv`` over the whole sequences: the same three products summed
    in the same order, so not a bit differs; the tail is kept in the
    inputs' dtype and holds them exactly."""
    dt = jnp.dtype(dtype)
    ch, width, slots = 64, 3, 3
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (2, 23, ch)).astype(dt)
    w = jax.random.normal(k2, (width, ch)) * width ** -0.5
    want = gd.causal_conv(x, w)
    # 30 layers' tails would be rows layer * (slots + 1) + slot: layer 1 here
    tails = jnp.full((2 * (slots + 1), (width - 1) * ch), 7.0, dt)
    base = slots + 1
    # sequence a in slot 2, b in slot 1: (tick's rows as (seq, t))
    ticks = [[(0, 0), (0, 1), (0, 2), (0, 3), (0, 4)],            # a starts
             [(0, 5), (1, 0), (1, 1), (1, 2)],                    # b starts
             [(0, 6), (1, 3), (1, 4), (1, 5), (1, 6), (1, 7)],
             [(0, 7), (1, 8)], [(0, 8), (1, 9)], [(1, 10), (0, 9)]]
    slot_of = {0: 2, 1: 1}
    got = {}
    for take in ticks:
        rows = jnp.stack([x[a, t] for a, t in take] + [x[0, 0]])  # + a dead row
        y, tails = gd.conv_tick(
            rows, w, tails, jnp.asarray([slot_of[a] for a, _ in take] + [0]),
            jnp.asarray([t for _, t in take] + [0]), base)
        assert tails.dtype == dt
        for (a, t), row in zip(take, y):
            got[a, t] = row
        assert not y[-1].any()
    for (a, t), row in got.items():
        np.testing.assert_array_equal(row, want[a, t])
    # layer 0's rows, and layer 1's null row, keep their bits
    assert (tails[:base + 1] == 7.0).all()
    # what a slot holds is its sequence's last two inputs, oldest first
    np.testing.assert_array_equal(
        tails[base + 2], jnp.concatenate([x[0, 8], x[0, 9]]))


def test_nothing_in_the_mixer_has_a_mean_over_tokens(model):
    """No activation function: two products of centred draws and a linear
    filter.  What the next layer's router reads holds no vector common to
    every token (models/sublayers.py ``_centred_down`` says what one costs
    a seeded router)."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["mixers"]["conv"])
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 128, 64))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    y, _ = sublayers.short_conv_sublayer(cfg, p, x)
    rows = np.asarray(y).reshape(-1, 64)
    common = np.square(rows.mean(0)).sum() / np.square(rows).sum(1).mean()
    assert common < 0.02, common            # 1 / 512 rows would be chance


# ---- the dense forward -----------------------------------------------------

def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jnp.asarray(prompts(150, 150, seed=1), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, MODEL)
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)
    # a bfloat16 computation of this float32 configuration is far outside it
    from unittest import mock

    with mock.patch.object(ref_common, "F32", jnp.bfloat16):
        low = ref.logits(params, tokens, MODEL).astype(jnp.float32)
    assert float(jnp.abs(jax.nn.log_softmax(low)
                         - jax.nn.log_softmax(want)).max()) > 100 * ATOL
    # and the plain trace is found again after the patched one
    again = ref.logits(params, tokens, MODEL)
    np.testing.assert_array_equal(again, want)


def test_dense_forward_of_a_held_share_matches_reference():
    """The chip's share through the whole stack: the router at 8 outputs,
    experts 4-5 held, the absent experts' terms left out in program and
    reference alike."""
    cfg = lfm2_cfg(moe_experts_held=2, moe_first_held_expert=4,
                   moe_capacity_factor=4.0)
    params = _drawn_norms(init_model_params(cfg, jax.random.PRNGKey(1)))
    assert params["mixers"]["experts"]["experts"]["fc2"]["kernel"].shape == (
        8, 2, 32, 64)
    tokens = jnp.asarray(prompts(60, seed=2), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, {**MODEL, "first_held_expert": 4})
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)
    other = ref.logits(params, tokens, {**MODEL, "first_held_expert": 0})
    assert float(jnp.abs(jax.nn.log_softmax(other)
                         - jax.nn.log_softmax(want)).max()) > 100 * ATOL


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: ONE expert layer of 16
    experts, each of 8 chips holding 2 of them and the router's 16 outputs
    and top-4.  The 8 chips' parts of the layer's output add up to what the
    uncut reference gives for the whole layer (no shared expert to count
    once)."""
    kw = dict(sublayer_pattern="CE", num_experts=16, moe_router_topk=4)
    whole_cfg = lfm2_cfg(**kw)
    p = jax.tree.map(lambda a: a[0], init_model_params(
        whole_cfg, jax.random.PRNGKey(4))["mixers"]["experts"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 37, 64))
    model = {**MODEL, "num_experts_per_tok": 4}
    stack = jax.tree.map(lambda a: a[None], p)
    want = ref.experts(stack, x, model, 0)
    program, _ = moe_mod.moe_sublayer(whole_cfg, p, x)
    np.testing.assert_allclose(program, want, rtol=0, atol=2e-5)
    total = 0.0
    for chip in range(8):
        cfg = lfm2_cfg(**kw, moe_experts_held=2,
                       moe_first_held_expert=2 * chip,
                       moe_capacity_factor=8.0)
        held = {**p, "experts": jax.tree.map(
            lambda a: a[2 * chip:2 * chip + 2], p["experts"])}
        part, aux = moe_mod.moe_sublayer(cfg, held, x)
        assert float(aux[5]) == 0              # no held assignment dropped
        # the reference given the same share: the same part
        ref_part = ref.experts(
            jax.tree.map(lambda a: a[None], held), x,
            {**model, "first_held_expert": 2 * chip}, 0)
        np.testing.assert_allclose(part, ref_part, rtol=0, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-5)


def test_the_residual_stream_and_the_tail_are_the_activations_dtype():
    """The family's code adds ``residual + mixer(norm(x))`` in the model's
    dtype and keeps its conv cache in it: a bfloat16 model's stream, conv
    inputs and tails are bfloat16."""
    cfg = lfm2_cfg(params_dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          init_model_params(cfg, jax.random.PRNGKey(0)))
    tokens = jnp.asarray(prompts(20, seed=5), jnp.int32)
    text = jax.jit(lambda p, t: model_forward(cfg, p, t)[0]).lower(
        params, tokens).as_text()
    assert "tensor<1x20x64xbf16>" in text
    assert "tensor<1x20x64xf32>" in text        # inside norms and the conv
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, MODEL)
    diff = jnp.abs(jax.nn.log_softmax(logits.astype(jnp.float32))
                   - jax.nn.log_softmax(want))
    assert 10 * ATOL < float(diff.mean()) < 0.05     # bfloat16, ten layers
    eng = engine(cfg, params)
    assert eng.spool.kv.conv.dtype == jnp.bfloat16 == eng.pool.kv.dtype


FAULTS = {
    "taps_reversed": ("conv_taps", lambda w: w[::-1]),
    "conv_acausal": ("conv_lookahead", lambda model: 1),
    "gate_b_dropped": ("in_gate", lambda b, u: u),
    "gate_c_dropped": ("out_gate", lambda cg, y: y),
    "tail_dropped_at_a_run": ("conv_restarts_every", lambda model: 16),
    "qk_norm_left_out": ("qk_normed", lambda model: False),
    "rope_left_out": ("rope_applied", lambda model: False),
    "bias_in_the_weight": ("weighed_scores", lambda s, biased: biased),
    "normalisation_dropped": ("gates_normalised", lambda model: False),
    "dense_layers_as_experts": ("dense_layers", lambda model: 0),
    "head_untied": ("head_kernel", lambda params: jnp.roll(
        params["embedding"]["word_embeddings"], 1, axis=0)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_fault_fails_the_comparison(model, fault, monkeypatch):
    """Every choice the reference exposes is ONE function, and turning it
    moves the comparison far outside what the honest program reads: a fault
    of that kind in the program would show."""
    cfg, params = model
    if fault in ("rope_left_out", "qk_norm_left_out"):
        # q and k entries of deviation 0.02 x sqrt(64) give scores of 0.03:
        # a softmax uniform whatever its keys.  Weigh them as the published
        # widths do (scores of deviation ~1), the out-projection with them
        params = jax.tree.map(lambda a: a, params)
        att = params["mixers"]["attention"]
        att["qkv"]["kernel"] = att["qkv"]["kernel"] * 6.0
        att["dense"]["kernel"] = att["dense"]["kernel"] * 4.0
    if fault == "bias_in_the_weight":
        params = jax.tree.map(lambda a: a, params)
        router = params["mixers"]["experts"]["router"]
        router["bias"] = router["bias"] * 10.0
    tokens = jnp.asarray(prompts(90, seed=11), jnp.int32)
    got = jax.nn.log_softmax(model_forward(cfg, params, tokens)[0])
    honest = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    assert float(jnp.abs(got - honest).max()) < ATOL
    name, other = FAULTS[fault]
    monkeypatch.setattr(ref, name, other)
    faulty = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    diff = np.abs(np.asarray(got - faulty))
    assert diff.max() > 100 * ATOL, (fault, diff.max())


# ---- through the engine: pages and tail slots -------------------------------

def _assert_idle(eng):
    assert isinstance(eng.pool, PagedKVPool) and not eng.pool.state
    assert isinstance(eng.spool, StatePool) and eng.cache is None
    assert eng.spool.num_free == eng.max_slots
    assert eng.pool.num_free == eng.pool.num_pages - 1
    assert not eng.pool.refcounts.any() and not eng.spool.refcounts.any()
    assert [cls.width for cls in eng._classes] == [eng.pages_per_seq, 1]
    assert_memory_idle(eng)


def test_engine_matches_reference_through_pages_and_tail_slots(model):
    """Prefill in chunks (100 and 37 tokens through runs of 16 rows: the
    tail crosses run boundaries), then decode, two requests of unequal
    length in the same ticks: the two attention layers from K/V pages
    (normed, rotated keys), the eight conv layers from the tail slot, the
    feed-forwards keeping nothing; then MORE requests than slots, so that
    slots and pages change hands, one of them a one-token prompt whose
    first row is a decode row at position 0."""
    cfg, params = model
    eng = engine(cfg, params, max_slots=2)
    assert eng.pool.kv.shape == (2, 65, PAGE, 2 * 2 * 16)   # TWO K/V layers
    assert eng.spool.kv._fields == ("conv",)                # tails ALONE
    assert eng.spool.kv.conv.shape == (8 * 3, 2 * 64)       # EIGHT conv ones
    assert eng.spool.kv.conv.dtype == jnp.float32           # the activations'
    assert eng.pages_per_seq == 256 // PAGE and eng._fill_end(100) == 99
    first = [eng.submit(p, 20, top_k=1, termination_id=NEVER)
             for p in prompts(100, 37)]
    eng.step()
    held = [r for r in first if r._phase != "queued"]
    # pages AND a slot: one record a class, the slot's of one entry
    assert held and all(r._mem[0].pages and len(r._mem[1].pages) == 1
                        for r in held)
    assert_memory(eng)
    eng.run_until_idle()
    later = [eng.submit(p, 12, top_k=1, termination_id=NEVER)
             for p in prompts(53, 1, 18, 70, seed=2)]
    eng.run_until_idle()
    for req in first + later:
        check(req, params)
    _assert_idle(eng)


def test_engine_serves_a_held_share_through_one_slot_in_turn():
    """The chip's share through the engine, and ONE slot: the second request
    takes the tail slot the first left, whatever it held, and starts from
    zeros at its position 0."""
    cfg = lfm2_cfg(moe_experts_held=2, moe_first_held_expert=4,
                   moe_capacity_factor=4.0)
    params = _drawn_norms(init_model_params(cfg, jax.random.PRNGKey(1)))
    eng = engine(cfg, params, max_slots=1)
    reqs = [eng.submit(p, 8, top_k=1, termination_id=NEVER)
            for p in prompts(45, 30, seed=7)]
    eng.run_until_idle()
    assert eng.spool.num_pages == 2          # the null slot and the one
    for req in reqs:
        check(req, params, {**MODEL, "first_held_expert": 4})
    assert eng.moe_held_experts_touched > 0
    _assert_idle(eng)


def test_preempted_and_recomputed_matches_never_preempted(model):
    """``preempt()`` drops the tail slot AND the pages and re-queues; the
    resume prefills both again from position 0, and counts the tokens."""
    cfg, params = model
    obs_registry.set_publishing(True)
    eng = engine(cfg, params)
    p, = prompts(60, seed=4)
    req = eng.submit(p, 30, top_k=1, termination_id=NEVER)
    while len(req.generated) < 11:
        eng.step()
    assert eng.preempt(req) and req._phase == "queued"
    assert not held_pages(req)
    assert_memory(eng)
    assert eng.spool.num_free == eng.max_slots
    done = len(req.generated)
    eng.run_until_idle()
    check(req, params)
    assert req._preemptions == 1 and eng.preemptions == 1
    assert eng.state_recomputed_tokens == len(p) + done - 1
    _assert_idle(eng)


def test_metrics_count_the_tails_the_pages_and_the_experts(model):
    cfg, params = model
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    eng = engine(cfg, params)
    names = ("state_rows", "state_touches", "state_steps", "state_resets",
             "paged_rows", "moe_assignments")
    before = {n: reg.counter(f"mlt_engine_{n}_total").value for n in names}
    for p in prompts(40, 1, seed=6):
        eng.submit(p, 6, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    got = {n: reg.counter(f"mlt_engine_{n}_total").value - before[n]
           for n in names}
    # 39 prompt rows in three runs (16 a tick) and 6 + 6 decode rows, one
    # lost to the tick that runs ahead of a stop: at least the live ones
    assert got["state_rows"] >= 39 + 12 and got["state_touches"] >= 3 + 12
    assert got["state_rows"] > got["state_touches"]
    # a conv tail has no sweep to plan: a pass a row
    assert got["state_steps"] == got["state_rows"]
    assert got["state_resets"] == 2          # one run at position 0 each
    assert got["paged_rows"] == got["state_rows"]
    assert got["moe_assignments"] > 0        # the eight expert layers' rows
    # the two classes' bytes apart: the tail slots and the K/V pages
    tails = 8 * 5 * 2 * 64 * 4
    pages = 2 * eng.pool.num_pages * PAGE * 2 * 2 * 16 * 4
    assert reg.gauge("mlt_engine_state_pool_bytes").value == \
        eng.spool.kv_pool_bytes() == tails
    assert reg.gauge("mlt_engine_kv_pool_bytes").value == \
        eng.pool.kv_pool_bytes() == pages


def test_pool_classes_memory_kind_and_bytes_at_published_widths():
    cfg = make_config("lfm2-24b-a2b", params_dtype="bfloat16",
                      moe_experts_held=8, moe_capacity_factor=8.0)
    pattern = cfg.model.sublayer_pattern
    page, state = pool_classes(cfg)
    assert memory_kind(cfg) == "tails" == memory_kind(lfm2_cfg())
    assert (page.name, page.state) == ("full", False)
    assert page.places == tuple(i for i, c in enumerate(pattern) if c == "*")
    assert state.name == "state" and state.state
    assert state.places == tuple(
        i for i, c in enumerate(pattern) if c == "C")
    assert (page.layers(cfg), state.layers(cfg)) == (10, 30)
    # abstract pools: 8 KiB of tail a layer and sequence, 2 KiB of keys and
    # values a token and layer (20 KiB a token in all)
    spool = jax.eval_shape(lambda: StatePool(cfg, 256, 16, layers=30).kv)
    assert spool._fields == ("conv",)
    assert spool.conv.shape == (30 * 257, 2 * 2048)
    assert spool.conv.dtype == jnp.bfloat16
    assert spool.conv.size * 2 == 30 * 257 * 8192
    pool = jax.eval_shape(lambda: PagedKVPool(
        cfg, 3, 16, layers=10, page_class="full").kv)
    assert pool.shape == (10, 3, 16, 2 * 8 * 64) and pool.dtype == jnp.bfloat16
    assert pool.size // (3 * 16) * 2 == 20 << 10
    # the weights this chip holds, counted by hand (benchmark/configs/
    # lfm2-24b-a2b.json reduced_why) against the tree's own shapes
    shapes = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    by_hand = (30 * (2048 * 6144 + 2048 * 2048 + 3 * 2048)
               + 10 * (2048 * 3072 + 2048 * 2048 + 2 * 64)
               + 2 * 3 * 2048 * 11776
               + 38 * (8 * 3 * 2048 * 1536 + 2048 * 64 + 64)
               + 65536 * 2048 + 81 * 2048)
    assert sum(a.size for a in jax.tree.leaves(shapes)) == by_hand
    assert 3.75e9 < by_hand < 3.77e9


def test_the_names_the_cells_readers_match():
    """``conv_share.lfm2`` and ``conv_roofline.lfm2`` read the scope
    ``short_conv``, ``expert_gemm_roofline.lfm2`` the scope
    ``moe/expert_gemm``, ``paged_attn_roofline.lfm2`` the paged kernel under
    ``attention``: a reader that matches nothing reports nothing and guards
    nothing."""
    cfg = lfm2_cfg()
    params = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    text = jax.jit(lambda p, t: model_forward(cfg, p, t)[0]).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("short_conv/in_proj", "short_conv/conv",
                  "short_conv/out_proj", "moe/expert_gemm",
                  "attention/global", "mlp"):
        assert scope in text, scope
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    readers = os.path.join(root, "benchmark", "layer_metrics")
    for name, needle in (
            ("conv_share.lfm2", '"short_conv"'),
            ("conv_roofline.lfm2", '"/short_conv/"'),
            ("expert_gemm_roofline.lfm2", '"/moe/expert_gemm/"')):
        with open(os.path.join(readers, name + ".py")) as f:
            assert needle in f.read(), name


# ---- what the tail class does not carry yet ---------------------------------

def _mesh(**kw):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**kw, data_parallel_size=1, devices=jax.devices()[:2])


REFUSED = [
    (dict(kv_dtype="int8"), "kv_dtype", "--kv_dtype int8"),
    (dict(kv_dtype="fp8"), "kv_dtype", "--kv_dtype fp8"),
    (dict(mesh="tp"), "tp", "tensor-parallel serving (tp 2)"),
    (dict(mesh="pp"), "pp", "pipeline-parallel serving (pp 2)"),
    (dict(draft=True), "draft", "--spec_k"),
    (dict(handoff=True), "handoff", "cross-replica KV handoff"),
    (dict(log_probs=True), "log_probs", "return_log_probs"),
]


@pytest.mark.parametrize("kw,feature,sentence", REFUSED,
                         ids=[s.split()[-1] if s.startswith("--kv")
                              else s.split()[0] for _, _, s in REFUSED])
def test_refuse_unserved_says_why(model, kw, feature, sentence):
    """The ``tails`` rows' sentences: what the class keeps is named as what
    it is (the conv's last inputs, no recurrent state, no float32 sum)."""
    cfg, _ = model
    kw = dict(kw)
    if kw.get("mesh") == "tp":
        kw["mesh"] = _mesh(tensor_model_parallel_size=2)
    elif kw.get("mesh") == "pp":
        kw["mesh"] = _mesh(pipeline_model_parallel_size=2)
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, **kw)
    text = str(e.value)
    assert sentence in text
    assert NOT_CARRIED["tails", feature].split("{")[0] in text
    assert (f"a stack of gated short convolutions (sublayer_pattern "
            f"{PATTERN}) keeps the conv's last inputs a sequence") in text
    assert KEEPS["tails"].split("{")[0] in text
    assert "float32 sum" not in text and "recurrent state a sequence" not in text


def test_a_held_share_is_refused_on_a_mesh_and_the_engine_refuses_too(model):
    held = lfm2_cfg(moe_experts_held=2, moe_capacity_factor=4.0)
    with pytest.raises(ValueError, match="moe_experts_held 2 of 8 is one "
                                         "chip's share"):
        refuse_unserved(held, mesh=_mesh(tensor_model_parallel_size=2))
    cfg, params = model
    with pytest.raises(ValueError, match="--kv_dtype fp8"):
        engine(cfg, params, kv_dtype="fp8")
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="return_log_probs"):
        eng.submit(prompts(12)[0], 4, return_log_probs=True)
    tokens = jnp.asarray(prompts(8), jnp.int32)
    with pytest.raises(AssertionError, match="a stack of one-sublayer "
                                             "layers runs the dense forward"):
        model_forward(cfg, params, tokens,
                      segment_ids=jnp.zeros((1, 8), jnp.int32))
