"""GigaChat3.5 (``gigachat35``: ai-sage GigaChat3.5-432B-A28B) on the normal
serving path, at tiny widths on the CPU: a HYBRID stack, one dense linear
layer, then a period of one gated latent-attention layer and three
gated-delta (linear) layers over routed experts, served through
``ContinuousBatchingEngine`` from TWO pools in one tick: latent pages for
the attention layer and a float32 state slot (``S`` and the conv's tail,
four layers') for the linear ones.  Everything is compared with the plain
reference (``benchmark/reference/gigachat35_block.py``: the recurrent form
a token at a time, the expanded latent attention) on the same weights."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import gigachat35_block as ref
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation.pools import (
    PagedKVPool,
    StatePool,
    memory_kind,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config, moe
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.models.transformer import (
    DELTA_CONV_OUT_STD,
    LayerKind,
    layer_kinds,
    pool_classes,
    stack_kinds,
)
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.ops import gated_delta as gd
from megatron_llm_tpu.ops import norms, rope
from megatron_llm_tpu.ops.retention import tick_runs
from tests.parity import assert_memory, assert_memory_idle, held_pages

# float32 rounding: the program sums a state's part and a run's part (the
# chunked form, the tick's runs) where the reference walks token by token,
# at log-probs of magnitude ~10 under init_method_std 0.3 (largest seen 4e-5)
ATOL = 1e-4
VOCAB = 256
NEVER = 10 ** 9
PAGE = 8

WIDTHS = dict(
    num_layers=4, dense_prefix_layers=1, hidden_size=64,
    num_attention_heads=4, ffn_hidden_size=128, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts=8, moe_router_topk=2,
    moe_ffn_hidden_size=32, vocab_size=VOCAB, max_position_embeddings=512,
    rope_yarn_original_max_position=64, seq_length=256,
    params_dtype="float32", use_flash_attn=False,
    # a share's row buffer takes every assignment: nothing is dropped
    moe_capacity_factor=4.0,
    # scores far enough apart that float32 rounding picks no other expert
    init_method_std=0.3)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6,
    rope_theta=100000, rope_scaling=dict(
        factor=8, original_max_position_embeddings=64, beta_fast=32,
        beta_slow=1, mscale=1, mscale_all_dim=1, type="yarn"),
    use_mla_scaling_factor=True, gated_attention=True,
    layernorm_type="pre_post", layernorm_gating_weight=2,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_sigmoid_gate_scale=2,
    linear_attn_o_norm_eps=1e-6, num_experts_per_tok=2,
    routed_scaling_factor=2.5, swiglu_limit=10, first_k_dense_replace=1,
    full_attention_layers=[1], first_held_expert=0)
HELD = 2


def giga_cfg(**kw):
    return make_config("gigachat35", **{**WIDTHS, **kw})


def _drawn(params):
    """The norms' leaves are zeros as initialised (a gain of exactly 1,
    where ``2 sigmoid(w)`` and ``1 + w`` agree): draw them, and the delta
    layers' head-norm weights, so that their form is compared."""
    def draw(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("['gate']") or "o_norm" in name:
            key = jax.random.PRNGKey(sum(map(ord, name)))
            return a + 0.3 * jax.random.normal(key, a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(draw, params)


@pytest.fixture(scope="module")
def model():
    cfg = giga_cfg()
    return cfg, _drawn(init_model_params(cfg, jax.random.PRNGKey(0)))


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


def check(req, params, atol=ATOL, model=MODEL):
    tokens, lps = req.result(timeout=120)
    want = reference_log_probs(params, tokens, model)[len(req.prompt) - 1:]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=atol)


# ---- the family ------------------------------------------------------------

def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert m.mla and m.delta and m.layer_period == 4 and m.depth == 5
    assert m.scanned_periods == 1
    assert layer_kinds(cfg) == (LayerKind(None, True),) + 3 * (
        LayerKind(None, True, "delta"),)
    # the dense prefix is a stack with its own kinds: linear layers
    assert stack_kinds(cfg, 0) == (LayerKind(None, True, "delta"),)
    assert stack_kinds(cfg, 1) == layer_kinds(cfg)
    page, state = pool_classes(cfg)
    assert (page.state, page.places, page.prefix) == (False, (0,), 0)
    assert (state.state, state.places, state.prefix) == (True, (1, 2, 3), 1)
    assert (page.layers(cfg), state.layers(cfg)) == (1, 4)
    assert memory_kind(cfg) == "hybrid"
    # the scanned stack holds no mixer: they are stacks of their own
    assert "attention" not in params["layers"]
    assert set(params["mixers"]) == {"attention", "delta"}
    mla, delta = params["mixers"]["attention"], params["mixers"]["delta"]
    assert mla["g_proj"]["kernel"].shape == (1, 64, 4 * 16)
    assert mla["q_norm"]["gate"].shape == (1, 48)
    assert delta["qkvz"]["kernel"].shape == (3, 64, 2 * 32 + 2 * 64)
    assert delta["ba"]["kernel"].shape == (3, 64, 8)
    assert delta["conv"]["kernel"].shape == (3, 4, 128)
    assert delta["a_log"].shape == delta["dt_bias"].shape == (3, 4)
    dense = params["dense_layers"]
    assert dense["attention"]["qkvz"]["kernel"].shape == (1, 64, 192)
    assert dense["mlp"]["fc1"]["kernel"].shape == (1, 64, 2, 128)
    for name in ("input_norm", "attn_out_norm", "post_norm", "mlp_out_norm"):
        assert set(dense[name]) == set(params["layers"][name]) == {"gate"}
    assert set(params["final_norm"]) == {"gate"}
    # under the bias alone a key 2,048 tokens back keeps 0.1-0.9
    fresh = init_model_params(cfg, jax.random.PRNGKey(3))["mixers"]["delta"]
    g = -jnp.exp(fresh["a_log"]) * jax.nn.softplus(fresh["dt_bias"])
    keep = np.exp(2048 * np.asarray(g))
    assert ((keep > 0.09) & (keep < 0.91)).all(), keep


def test_q_k_and_v_enter_silu_where_it_is_nearly_linear(model):
    """The conv's filter is drawn for it (``DELTA_CONV_OUT_STD``): at a
    deviation of ~1.7 SiLU's mean on every channel makes every key side
    with every query, the layer emit one vector for all tokens, and the
    router after it send a tick's rows to the same experts."""
    cfg, _ = model
    fresh = init_model_params(cfg, jax.random.PRNGKey(5))["mixers"]["delta"]
    key = jax.random.PRNGKey(6)
    u = jax.random.normal(key, (2, 400, cfg.model.hidden_size))
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))     # a normed row
    for layer in range(3):
        qkv = u @ fresh["qkvz"]["kernel"][layer][:, :128]
        x = gd.causal_conv(qkv, fresh["conv"]["kernel"][layer])[:, 3:]
        assert 0.8 * DELTA_CONV_OUT_STD < float(x.std()) < 1.2 * DELTA_CONV_OUT_STD
        y = jax.nn.silu(x)
        # SiLU's mean is a 20th of its deviation there (a half at 1.7)
        assert abs(float(y.mean())) < 0.1 * float(y.std())


def test_a_uniform_model_keeps_its_tree_and_its_kinds():
    cfg = make_config("llama2", num_layers=2, hidden_size=64,
                      num_attention_heads=4, vocab_size=128,
                      params_dtype="float32", use_flash_attn=False)
    assert layer_kinds(cfg) == stack_kinds(cfg, 0) == (LayerKind(None, True),)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    assert "mixers" not in params and set(params["layers"]["attention"]) == {
        "qkv", "dense"}
    assert set(params["final_norm"]) == {"scale"}


def test_yarn_frequencies_and_the_softmax_scale():
    """Pairs that turn often over the original context keep their
    frequency, slow ones are divided by the factor, and m^2 scales the
    softmax: 0.1 ln 8 + 1 = 1.2079."""
    freqs = 1.0 / (100000.0 ** (np.arange(0, 64, 2) / 64))
    got = np.asarray(rope.yarn_scale_freqs(
        jnp.asarray(freqs, jnp.float32), 8.0, 100000.0, 32.0, 1.0, 32768))
    assert np.allclose(got[:9], freqs[:9], rtol=1e-6)        # extrapolated
    assert np.allclose(got[-8:], freqs[-8:] / 8, rtol=1e-6)  # interpolated
    assert ((got <= freqs * (1 + 1e-6)) & (got >= freqs / 8 * (1 - 1e-6))).all()
    assert abs(rope.yarn_mscale(8.0, 1.0) - 1.2079) < 1e-4
    assert rope.yarn_mscale(1.0, 1.0) == rope.yarn_mscale(8.0, 0.0) == 1.0
    assert abs(ref.softmax_scale({**MODEL, "qk_nope_head_dim": 128,
                                  "qk_rope_head_dim": 64})
               - 192 ** -0.5 * 1.2079 ** 2) < 1e-5


def test_the_gain_and_the_clamp():
    w = jnp.asarray([-1.0, 0.0, 2.0])
    x = jnp.asarray([[3.0, -4.0, 12.0]])
    got = norms.norm(x, {"gate": w}, 1e-6, True)
    rms = np.sqrt((9 + 16 + 144) / 3)
    np.testing.assert_allclose(
        got[0], np.asarray(x[0]) / rms * 2 / (1 + np.exp(-np.asarray(w))),
        rtol=1e-5)
    from megatron_llm_tpu.ops.activations import glu_product

    value, gate = jnp.asarray([20.0, -20.0, 1.0]), jnp.asarray([20.0, 1.0, -20.0])
    np.testing.assert_allclose(
        glu_product("swiglu", value, gate, 10.0),
        np.clip(value, -10, 10) * jax.nn.silu(jnp.minimum(gate, 10.0)))
    np.testing.assert_allclose(glu_product("swiglu", value, gate),
                               value * jax.nn.silu(gate))


# ---- the forms of the rule ---------------------------------------------------

def _rows(seed, b, s, hk=2, hv=4, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gd.l2_normalize(jax.random.normal(ks[0], (b, s, hk, d))) * d ** -0.5
    k = gd.l2_normalize(jax.random.normal(ks[1], (b, s, hk, d)))
    v = jax.random.normal(ks[2], (b, s, hv, d))
    g = -0.2 * jax.nn.softplus(jax.random.normal(ks[3], (b, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("s,chunk", [(1, 64), (7, 4), (64, 64), (150, 64)])
def test_recurrent_chunked_and_attention_forms_agree(s, chunk):
    rows = _rows(s, 2, s)
    want, _ = gd.delta_recurrent(*rows)
    np.testing.assert_allclose(gd.delta_attention(*rows), want, atol=2e-5)
    np.testing.assert_allclose(gd.delta_chunked(*rows, chunk=chunk), want,
                               atol=2e-5)


def test_the_chunked_form_is_differentiable():
    rows = _rows(3, 1, 20)
    grads = jax.grad(lambda *r: gd.delta_chunked(*r, chunk=8).sum(),
                     argnums=(0, 1, 2, 3, 4))(*rows)
    want = jax.grad(lambda *r: gd.delta_recurrent(*r)[0].sum(),
                    argnums=(0, 1, 2, 3, 4))(*rows)
    for a, b in zip(grads, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=2e-4)


# ticks of (slot, sequence, from, to): two sequences on slots 2 and 4 fed
# in runs of unequal length, decode rows between prompt runs, dead rows,
# and slot 2 taken over by a THIRD sequence that starts at position 0
TICKS = [
    [(2, 0, 0, 10), (0, 0, 0, 1), (4, 1, 0, 5), (0, 0, 0, 2)],
    [(2, 0, 10, 11), (4, 1, 5, 6), (0, 0, 3, 4)],
    [(2, 0, 11, 12), (4, 1, 6, 20)],
    [(4, 1, 20, 21), (2, 2, 0, 2)],           # slot 2 changes hands
    [(2, 2, 2, 3), (4, 1, 21, 22)],
    [(2, 2, 3, 9)],
]


def _feed(tick, rows):
    slots, pos, take = [], [], []
    for slot, seq, lo, hi in tick:
        for t in range(lo, hi):
            slots.append(slot)
            pos.append(t if slot else 0)
            take.append((seq, t))
    args = [jnp.stack([r[a, t] for a, t in take]) for r in rows]
    return (args, jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32),
            take)


def test_the_ticks_form_carries_state_and_tail_across_runs_and_slot_reuse():
    """Layer 1 of a two-layer pool that starts as noise: runs of one
    sequence in different ticks continue its state and its conv tail, a run
    at position 0 starts from zero whatever the slot held, dead rows and
    the other layer touch nothing."""
    rows = _rows(7, 3, 24)
    want, _ = gd.delta_recurrent(*rows)
    c = 12
    w = jax.random.normal(jax.random.PRNGKey(8), (4, c))
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 24, c))
    want_conv = gd.causal_conv(x, w)
    pool = gd.DeltaState(
        jax.random.normal(jax.random.PRNGKey(1), (2, 6, 4, 16, 16)),
        jax.random.normal(jax.random.PRNGKey(2), (2 * 6, 3 * c)))
    start = pool
    for tick in TICKS:
        args, slots, pos, take = _feed(tick, rows)
        o, s = gd.delta_tick(*args, pool.s, slots, pos, 1)
        y, tails = gd.conv_tick(jnp.stack([x[a, t] for a, t in take]), w,
                                pool.conv, slots, pos, 1 * 6)
        pool = gd.DeltaState(s, tails)
        for i, (seq, t) in enumerate(take):
            if slots[i]:
                np.testing.assert_allclose(o[i], want[seq, t], atol=2e-5)
                np.testing.assert_allclose(y[i], want_conv[seq, t], atol=2e-5)
            else:
                assert not np.asarray(o[i]).any() and not np.asarray(y[i]).any()
    np.testing.assert_array_equal(pool.s[0], start.s[0])
    np.testing.assert_array_equal(pool.conv[:6], start.conv[:6])
    for slot in (1, 3, 5):                       # slots no row named
        np.testing.assert_array_equal(pool.s[1, slot], start.s[1, slot])
        np.testing.assert_array_equal(pool.conv[6 + slot],
                                      start.conv[6 + slot])


# ---- the tick's write of the conv tails --------------------------------------

def rowwise_tails(tails, x, slots, positions, base):
    """What ``conv_tick``'s write left until PR 51, row by row in the tick's
    order: EVERY row sets a row of the pool to its last three inputs (what
    its run fed, behind the run's start the slot's tail, zeros where the run
    starts a sequence), a run's last row its slot's, any other row the
    layer's null row."""
    before, tails = np.asarray(tails), np.array(tails)
    live, first, fresh = (np.asarray(t) for t in tick_runs(slots, positions))
    c = x.shape[1]
    for i, slot in enumerate(np.asarray(slots)):
        if first[i] or not live[i]:
            ins = [np.zeros(c, np.float32)] * 3 if fresh[i] or not live[i] \
                else list(before[base + slot].reshape(3, c))
        ins = ins[1:] + [np.asarray(x[i], np.float32)]
        ends = live[i] and not (i + 1 < len(live) and live[i + 1]
                                and not first[i + 1])
        tails[base + (slot if ends else 0)] = np.concatenate(ins)
    return tails


def _tick(tick, base=6, c=12, seed=9):
    """(x, slots, positions) of one tick of ``TICKS``' notation."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 300, c))
    _, slots, pos, take = _feed(tick, [])
    return jnp.stack([x[a, t] for a, t in take]), slots, pos


def _assert_tails(got, want, nulls):
    """Bit for bit in every row but the layers' null rows."""
    rows = np.setdiff1d(np.arange(len(want)), nulls)
    np.testing.assert_array_equal(np.asarray(got)[rows], want[rows])


def test_the_tails_after_each_tick_are_the_rowwise_writes_bit_for_bit():
    w = jax.random.normal(jax.random.PRNGKey(8), (4, 12))
    start = jax.random.normal(jax.random.PRNGKey(2), (2 * 6, 3 * 12))
    tails = start
    for tick in TICKS:
        x, slots, pos = _tick(tick)
        want = rowwise_tails(tails, x, slots, pos, 6)
        _, tails = gd.conv_tick(x, w, tails, slots, pos, 6)
        _assert_tails(tails, want, [0, 6])
        # the null rows, which took the rows that end no run, take nothing
        np.testing.assert_array_equal(tails[jnp.asarray([0, 6])],
                                      start[jnp.asarray([0, 6])])


R_ROWS = 9
RUNS = {
    "every row dead": [(0, 0, 0, R_ROWS)],
    "a run that ends on the last row": [(0, 0, 0, 3), (3, 0, 4, 10)],
    "a run of one row, last": [(2, 0, 5, 13), (1, 1, 7, 8)],
    "runs of one row": [(s, s % 3, 2 * s, 2 * s + 1) for s in (5, 3, 1, 4, 2)],
    "a run of R rows": [(4, 2, 0, R_ROWS)],
    "a run of R rows that goes on": [(4, 2, 17, 17 + R_ROWS)],
    "two runs name one slot: the later": [(3, 0, 0, 4), (1, 1, 5, 6),
                                          (3, 2, 0, 3)],
}


@pytest.mark.parametrize("name", RUNS)
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_a_runs_last_row_writes_and_only_its_layers_row(name, layer):
    """Three layers of six rows: the rows of the layer named equal the
    rowwise writes', every other row of the pool, the null rows among
    them, keeps its bits."""
    w = jax.random.normal(jax.random.PRNGKey(8), (4, 12))
    start = jax.random.normal(jax.random.PRNGKey(3), (3 * 6, 3 * 12))
    x, slots, pos = _tick(RUNS[name], seed=4)
    want = rowwise_tails(start, x, slots, pos, layer * 6)
    got = np.asarray(gd.conv_tick(x, w, start, slots, pos, layer * 6)[1])
    _assert_tails(got, want, [layer * 6])
    named = layer * 6 + np.unique(np.asarray(slots)[np.asarray(slots) > 0])
    rest = np.setdiff1d(np.arange(3 * 6), named)
    np.testing.assert_array_equal(got[rest], np.asarray(start)[rest])
    assert (got[named] != np.asarray(start)[named]).any(axis=1).all()


@pytest.mark.parametrize("rows,per,layers", [(3, 300, 2), (256, 129, 4)])
def test_the_write_walks_a_pool_larger_than_its_block(rows, per, layers):
    """``PUT_ROWS`` at a time from the lowest row named to the highest: a
    layer of 300 rows named at both ends, and the cell's pool."""
    c = 4
    rng = np.random.default_rng(rows)
    slots = np.zeros(rows, np.int32)
    picked = rng.permutation(np.arange(2, per - 1))[:rows - 3]
    slots[:len(picked) + 2] = [1, *picked, per - 1]         # the rest: dead
    slots, pos = jnp.asarray(slots), jnp.full((rows,), 7, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(5), (rows, c))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, c))
    start = jax.random.normal(jax.random.PRNGKey(7), (layers * per, 3 * c))
    base = (layers - 1) * per
    want = rowwise_tails(start, x, slots, pos, base)
    got = jax.jit(gd.conv_tick)(x, w, start, slots, pos, base)[1]
    _assert_tails(got, want, [base])
    np.testing.assert_array_equal(got[:base + 1], start[:base + 1])


def _wide_scatters(text, rows):
    """The scatters of a lowered module whose updates hold more than a
    word a row, by the updates' type."""
    updates = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*, (tensor<[^>]*>)\) ->', text,
        flags=re.S)
    assert len(updates) == text.count("stablehlo.scatter\"(")
    return [u for u in updates
            if math.prod(int(d) for d in re.findall(r"(\d+)x", u)) > rows]


def test_the_lowered_tick_scatters_no_row_of_tails():
    """A scatter of ``[R, 3c]`` updates reaches the TPU as a loop of R
    dynamic-update-slices (10% of the GigaChat cell until PR 51) and reads
    the same as any other form on a CPU: so the lowered text is read."""
    rows, c = 256, 8
    args = (jnp.zeros((rows, c)), jnp.zeros((4, c)),
            jnp.zeros((4 * 129, 3 * c)), jnp.zeros((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.int32(129))
    assert not _wide_scatters(
        jax.jit(gd.conv_tick).lower(*args).as_text(), rows)
    # the probe sees the write it guards against
    before = jax.jit(lambda t, new, to: t.at[to].set(new)).lower(
        args[2], jnp.zeros((rows, 3 * c)), args[3]).as_text()
    assert len(_wide_scatters(before, rows)) == 1


# ---- the model against the reference ---------------------------------------

def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jnp.asarray(prompts(100, 100, seed=1), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, MODEL)
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)


FLIPPED = {
    "gain_one_plus_w": ("norm_gain", lambda w, model: 1.0 + w),
    "no_after_norms": ("post_norms", lambda model: False),
    "no_mla_scaling": ("softmax_scale", lambda model: 24 ** -0.5),
    "attention_ungated": ("attention_gate", lambda p, u: 1.0),
    "decay_ignored": ("delta_decay", lambda p, u, hv: 0.0 * (
        u @ p["ba"]["kernel"])[..., hv:]),
    "beta_one": ("write_strength", lambda p, u, hv: 1.0 + 0.0 * (
        u @ p["ba"]["kernel"])[..., :hv]),
    "conv_tail_dropped": ("conv_reset_every", lambda model: 16),
    "no_clamp": ("swiglu_limit", lambda model: None),
}


@pytest.mark.parametrize("choice", list(FLIPPED))
def test_each_choice_of_the_reference_is_seen_by_the_comparison(
        model, choice, monkeypatch):
    """Every reading the config leaves to the modelling file is ONE
    function of the reference, and flipping it moves the comparison far
    outside what the honest program reads: the program implements the
    reading the configuration file's ``assumed`` states, and a fault of
    that kind in it would show."""
    cfg, params = model
    if choice == "no_clamp":
        # the clamp bites where a pre-activation passes 10: scale one up
        params = jax.tree.map(lambda a: a, params)
        fc1 = params["dense_layers"]["mlp"]["fc1"]["kernel"]
        params["dense_layers"]["mlp"]["fc1"]["kernel"] = fc1 * 12.0
    tokens = jnp.asarray(prompts(90, seed=11), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    got = jax.nn.log_softmax(logits)
    honest = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    assert float(jnp.abs(got - honest).max()) < ATOL
    name, other = FLIPPED[choice]
    monkeypatch.setattr(ref, name, other)
    flipped = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    assert float(jnp.abs(got - flipped).max()) > 100 * ATOL, choice


def test_the_output_gates_scale_is_cancelled_by_the_after_norm(
        model, monkeypatch):
    """``linear_sigmoid_gate_scale`` 2 multiplies a linear layer's whole
    output, which ``W_out`` passes on and the after-norm N2 of ``pre_post``
    divides out again (an RMSNorm forgets its input's scale, up to eps): no
    comparison of logits can tell 2 from 1, here or on the chip."""
    _, params = model
    tokens = jnp.asarray(prompts(90, seed=11), jnp.int32)
    # eps shows at these widths (a branch's mean square is ~1e-3 of 1e-6
    # where q, k and v enter SiLU at 0.1): under 0.005 nats at the
    # model's eps, nothing at all without one
    for eps, limit in ((MODEL["rms_norm_eps"], 50 * ATOL), (1e-12, ATOL)):
        tiny = dict(MODEL, rms_norm_eps=eps)
        monkeypatch.setattr(ref, "output_gate_scale", lambda model: 2.0)
        honest = jax.nn.log_softmax(ref.logits(params, tokens, tiny))
        monkeypatch.setattr(ref, "output_gate_scale", lambda model: 1.0)
        other = jax.nn.log_softmax(ref.logits(params, tokens, tiny))
        assert float(jnp.abs(honest - other).max()) < limit
    # ... but not without the after-norms
    monkeypatch.setattr(ref, "post_norms", lambda model: False)
    third = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    monkeypatch.setattr(ref, "output_gate_scale", lambda model: 2.0)
    fourth = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    assert float(jnp.abs(third - fourth).max()) > 100 * ATOL


def share_of(params, first, held):
    """The tree of the chip that holds experts ``first .. first + held``."""
    layers = dict(params["layers"])
    m = dict(layers["moe"])
    m["experts"] = jax.tree.map(lambda a: a[:, first:first + held],
                                m["experts"])
    layers["moe"] = m
    return {**params, "layers": layers}


def test_shares_sum_to_the_uncut_layer(model):
    """The guide's share test: the four shares' routed parts plus the
    shared expert counted ONCE equal the uncut reference layer (biased
    sigmoid router, top-2 normalised over both chosen, times 2.5; the
    clamp in routed and shared experts alike)."""
    cfg, params = model
    layer = jax.tree.map(lambda a: a[2], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ht = x.reshape(48, 64)
        uncut = ref.moe(layer, ht, MODEL)
        shared = ref.swiglu(ht, layer["shared"]["fc1"]["kernel"],
                            layer["shared"]["fc2"]["kernel"], MODEL)
    total = 0.0
    for first in range(0, 8, HELD):
        scfg = giga_cfg(moe_experts_held=HELD, moe_first_held_expert=first)
        p = {**layer, "experts": jax.tree.map(
            lambda a: a[first:first + HELD], layer["experts"])}
        out, aux = moe.moe_sublayer(scfg, p, x)
        assert float(aux[5]) == 0 and float(aux[6]) <= HELD
        total = total + (out.reshape(48, 64) - shared)     # its routed part
        # and the reference, given the same share as data, reads the same
        with jax.default_matmul_precision("highest"):
            part = ref.moe(p, ht, {**MODEL, "first_held_expert": first})
        np.testing.assert_allclose(out.reshape(48, 64), part, atol=1e-4)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               rtol=0, atol=1e-4)


# ---- through the engine: both pools ----------------------------------------

def _assert_idle(eng):
    assert isinstance(eng.pool, PagedKVPool) and not eng.pool.state
    assert isinstance(eng.spool, StatePool) and eng.cache is None
    assert eng.spool.num_free == eng.max_slots
    assert eng.pool.num_free == eng.pool.num_pages - 1
    assert not eng.pool.refcounts.any() and not eng.spool.refcounts.any()
    assert [cls.width for cls in eng._classes] == [eng.pages_per_seq, 1]
    assert_memory_idle(eng)


def test_engine_matches_reference_through_both_pools(model):
    """Prefill in chunks, then decode, two requests of unequal length in
    the same ticks: the latent layer from pages, the four linear layers
    from the state slot; then MORE requests than slots, so that slots and
    pages change hands, one of them a one-token prompt whose first row is a
    decode row at position 0."""
    cfg, params = model
    eng = engine(cfg, params, max_slots=2)
    assert eng.pool.kv.shape == (1, 65, PAGE, 128)       # ONE latent layer
    assert eng.spool.kv.s.shape == (4, 3, 4, 16, 16)     # FOUR linear ones
    assert eng.spool.kv.conv.shape == (4 * 3, 3 * 128)
    assert eng.spool.kv.s.dtype == eng.spool.kv.conv.dtype == jnp.float32
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


def test_a_held_share_is_served_and_matches_its_reference(model):
    cfg, params = model
    scfg = giga_cfg(moe_experts_held=HELD, moe_first_held_expert=4)
    sparams = share_of(params, 4, HELD)
    eng = engine(scfg, sparams)
    reqs = [eng.submit(p, 10, top_k=1, termination_id=NEVER)
            for p in prompts(40, 21, seed=3)]
    eng.run_until_idle()
    for req in reqs:
        check(req, sparams, model={**MODEL, "first_held_expert": 4})


def test_preempted_and_recomputed_matches_never_preempted(model):
    """``preempt()`` drops the state AND the pages and re-queues; the
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
    # every token before the last was prefilled again
    assert eng.state_recomputed_tokens == len(p) + done - 1
    _assert_idle(eng)


def test_metrics_count_the_state_and_the_pages(model):
    cfg, params = model
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    eng = engine(cfg, params)
    names = ("state_rows", "state_touches", "state_steps", "state_resets",
             "paged_rows", "paged_walks")
    before = {n: reg.counter(f"mlt_engine_{n}_total").value for n in names}
    a, b = prompts(40, 1, seed=6)
    for p in (a, b):
        eng.submit(p, 6, top_k=1, termination_id=NEVER)
    eng.step()
    # a gauge a pool class for what is in use: state slots, latent pages
    in_use = {cls: reg.gauge("mlt_engine_pool_pages", labels={
        "class": cls, "state": "referenced"}) for cls in ("full", "state")}
    eng.run_until_idle()
    got = {n: reg.counter(f"mlt_engine_{n}_total").value - before[n]
           for n in names}
    # 39 prompt rows in three runs (16 a tick) and 6 + 6 decode rows, one
    # lost to the tick that runs ahead of a stop: at least the live ones
    assert got["state_rows"] >= 39 + 12 and got["state_touches"] >= 3 + 12
    assert got["state_rows"] > got["state_touches"]
    # the delta sweep walks a run's rows: a pass over the state a live row
    assert got["state_steps"] == got["state_rows"]
    assert got["state_resets"] == 2          # one run at position 0 each
    # the same rows went through the paged kernel, whose walks are a tile's
    assert got["paged_rows"] == got["state_rows"]
    assert 0 < got["paged_walks"] <= got["paged_rows"]
    assert in_use["full"].value == 0 and in_use["state"].value == 0
    assert reg.gauge("mlt_engine_state_pool_bytes").value == \
        eng.spool.kv_pool_bytes() == 4 * 5 * (4 * 16 * 16 + 3 * 128) * 4


def test_the_names_the_cells_readers_match():
    """``delta_share`` reads the scope ``attention/delta``,
    ``delta_roofline`` the kernel ``delta_sweep``, ``mla_attn_roofline``
    the paged kernel under ``attention/mla``: a reader that matches nothing
    reports nothing and guards nothing."""
    from megatron_llm_tpu.ops.pallas import gated_delta as kernel

    assert kernel.NAME == "delta_sweep"
    cfg = giga_cfg()
    params = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    text = jax.jit(lambda p, t: model_forward(cfg, p, t)[0]).lower(
        params, tokens).as_text(debug_info=True)
    assert "attention/delta" in text and "attention/mla" in text
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    readers = os.path.join(root, "benchmark", "layer_metrics")
    for name, needle in (("delta_share.gigachat", '"attention/delta"'),
                         ("delta_roofline.gigachat", '"delta_sweep"'),
                         ("mla_attn_roofline.gigachat", '"paged_attention"')):
        with open(os.path.join(readers, name + ".py")) as f:
            assert needle in f.read(), name


# ---- what the hybrid does not carry yet ------------------------------------

def _mesh(**kw):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**kw, data_parallel_size=1, devices=jax.devices()[:2])


REFUSED = [
    (dict(kv_dtype="int8"), "--kv_dtype int8"),
    (dict(mesh="tp"), "tensor-parallel serving (tp 2)"),
    (dict(mesh="pp"), "pipeline-parallel serving (pp 2)"),
    (dict(draft=True), "--spec_k"),
    (dict(handoff=True), "cross-replica KV handoff"),
    (dict(log_probs=True), "return_log_probs"),
]


@pytest.mark.parametrize("kw,sentence", REFUSED,
                         ids=[s.split()[0] for _, s in REFUSED])
def test_refuse_unserved_says_why(model, kw, sentence):
    cfg, _ = model
    kw = dict(kw)
    if kw.get("mesh") == "tp":
        kw["mesh"] = _mesh(tensor_model_parallel_size=2)
    elif kw.get("mesh") == "pp":
        kw["mesh"] = _mesh(pipeline_model_parallel_size=2)
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, **kw)
    assert sentence in str(e.value)
    assert "a hybrid stack (linear_layout (0, 1, 1, 1))" in str(e.value)


def test_engine_refuses_at_start_up_and_at_the_request(model):
    cfg, params = model
    with pytest.raises(ValueError, match="--kv_dtype fp8"):
        engine(cfg, params, kv_dtype="fp8")
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="return_log_probs"):
        eng.submit(prompts(12)[0], 4, return_log_probs=True)
