"""NVIDIA Nemotron-H (``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B) on the
normal serving path, at tiny widths on the CPU: a stack of ONE-SUBLAYER
layers in an order that holds ``M*``, ``*E`` and ``EM`` side by side,
Mamba-2 layers (2 groups of B and C) on float32 state slots, non-gated
relu^2 expert layers over a held share, NoPE GQA layers on K/V pages,
served through ``ContinuousBatchingEngine`` from TWO pools in one tick.
Everything is compared with the plain reference
(``benchmark/reference/nemotron_h_block.py``: the recurrence a token at a
time) on the same weights, logits and not tokens."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import nemotron_h_block as ref
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation.pools import (
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
from megatron_llm_tpu.ops import mamba2 as mb
from tests.parity import assert_memory, assert_memory_idle, held_pages

# float32 rounding: the program sums a state's part and a run's part (the
# chunked form, the tick's runs) where the reference walks token by token,
# at log-probs of magnitude ~5 (largest seen 2e-6)
ATOL = 2e-5
VOCAB = 256
NEVER = 10 ** 9
PAGE = 8
PATTERN = "M*EMME*E"           # M*, *E, EM, MM, ME, E* side by side
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

WIDTHS = dict(
    sublayer_pattern=PATTERN, hidden_size=64, num_attention_heads=4,
    num_attention_heads_kv=2, kv_channels=16, mamba_num_heads=8,
    mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=16,
    num_experts=8, moe_router_topk=2, moe_ffn_hidden_size=32,
    ffn_hidden_size=32, moe_shared_experts=2, moe_routed_scaling_factor=2.5,
    vocab_size=VOCAB, max_position_embeddings=512, seq_length=256,
    params_dtype="float32", use_flash_attn=False)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(
    hybrid_override_pattern=PATTERN, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, layer_norm_epsilon=1e-5,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_shared_experts=1,
    rope_theta=10000)


def nemotron_cfg(**kw):
    return make_config("nemotron_h", **{**WIDTHS, **kw})


def _drawn_norms(params, seed=3):
    """The initialiser leaves every norm's scale at 1; draw them, so that a
    norm the program forgot or misplaced would show."""
    key = jax.random.PRNGKey(seed)
    out = jax.tree.map(lambda a: a, params)
    for i, node in enumerate((out["layers"]["input_norm"], out["final_norm"],
                              out["mixers"]["mamba"]["o_norm"])):
        node["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), node["scale"].shape)
    return out


@pytest.fixture(scope="module")
def model():
    cfg = nemotron_cfg()
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
    assert m.mamba and not m.delta and not m.mla and not m.retention
    assert m.num_layers == m.depth == m.layer_period == len(PATTERN)
    assert m.position_embedding_type == "none" and not m.tie_embed_logits
    assert m.glu_activation is None and m.activation == "squared_relu"
    assert layer_kinds(cfg) == tuple(
        LayerKind(None, False, sublayers.SUBLAYERS[c]) for c in PATTERN)
    # a layer holds ONE norm; its one sublayer is its kind's stack's
    assert set(params["layers"]) == {"input_norm"}
    assert params["layers"]["input_norm"]["scale"].shape == (8, 64)
    mamba, att, exp = (params["mixers"][k]
                       for k in ("mamba", "attention", "experts"))
    # z 64 | x 64 | B 32 | C 32 | dt 8; the conv over x, B, C with a bias
    assert mamba["in_proj"]["kernel"].shape == (3, 64, 64 + 128 + 8)
    assert mamba["conv"]["kernel"].shape == (3, 4, 128)
    assert mamba["conv"]["bias"].shape == (3, 128)
    assert mamba["o_norm"]["scale"].shape == (3, 64)
    assert att["qkv"]["kernel"].shape == (2, 64, (4 + 2 * 2) * 16)
    assert exp["router"]["kernel"].shape == (3, 64, 8)
    assert exp["router"]["bias"].shape == (3, 8)
    # not gated; the up-projection stored transposed (32 is off the 128 grid)
    assert exp["experts"]["fc1"]["kernel"].shape == (3, 8, 32, 64)
    assert exp["experts"]["fc2"]["kernel"].shape == (3, 8, 32, 64)
    assert exp["shared"]["fc1"]["kernel"].shape == (3, 64, 2 * 32)


def test_the_published_string_is_23_23_6():
    cfg = make_config("nemotron_h-3-nano-30b-a3b")
    m = cfg.model
    assert m.sublayer_pattern == PUBLISHED and m.num_layers == 52
    kinds = [k.mixer for k in layer_kinds(cfg)]
    assert [kinds.count(k) for k in ("mamba", "experts", "attention")] == [
        23, 23, 6]
    assert not any(k.rotate for k in layer_kinds(cfg))
    assert (m.hidden_size, m.mamba_num_heads, m.mamba_head_dim,
            m.mamba_n_groups, m.ssm_state_size) == (2688, 64, 64, 8, 128)
    assert m.mamba_conv_channels == 6144
    assert (m.num_experts, m.moe_router_topk, m.moe_ffn_hidden_size,
            m.moe_shared_experts, m.moe_routed_scaling_factor) == (
        128, 6, 1856, 2, 2.5)
    assert (m.num_attention_heads, m.num_attention_heads_kv,
            m.kv_channels) == (32, 2, 128)
    # its repeated stretches are scanned: 14 layer bodies for 52 layers
    units = sublayers.stretches(PUBLISHED)
    assert "".join(u * c for u, c in units) == PUBLISHED
    assert sum(len(u) for u, _ in units) == 14


@pytest.mark.parametrize("pattern,want", [
    ("M", (("M", 1),)),
    ("MMMM", (("M", 4),)),
    ("M*EEM", (("M*", 1), ("E", 2), ("M", 1))),
    ("EMEMEM*EMEMEM*EMEMEM*", (("EMEMEM*", 3),)),      # 7 bodies, not 9
    ("EMEMEM*EMEMEM*", (("EM", 3), ("*", 1), ("EM", 3), ("*", 1))),   # 6
])
def test_stretches_cover_any_string(pattern, want):
    assert sublayers.stretches(pattern) == want
    assert "".join(u * c for u, c in want) == pattern


def test_a_dash_and_another_letter_are_refused_in_a_sentence():
    with pytest.raises(AssertionError, match="'-' is the Nemotron-H family's "
                                             "dense MLP layer"):
        nemotron_cfg(sublayer_pattern="M-M*")
    with pytest.raises(AssertionError, match="a letter a layer, M "):
        nemotron_cfg(sublayer_pattern="MxE")
    with pytest.raises(AssertionError, match="an E layer is an expert block"):
        nemotron_cfg(sublayer_pattern="M*E", num_experts=None,
                     moe_shared_experts=0)


def test_the_initialiser_draws_what_makes_the_recurrence_non_trivial(model):
    """``A`` in (1, 16), the step under the bias alone log-uniform in
    [0.001, 0.1], ``D`` ones, a drawn conv bias: zeros there would give
    every head one half-life and the comparison nothing to see.  The z
    columns of the in-projection are drawn narrower, so that the gate is
    centred and hands the next layer's router no common vector."""
    cfg, _ = model
    mamba = init_model_params(cfg, jax.random.PRNGKey(0))["mixers"]["mamba"]
    a = np.exp(np.asarray(mamba["a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    lo, hi = sublayers.MAMBA_TIME_STEP
    assert (lo, hi, sublayers.MAMBA_TIME_STEP_FLOOR) == (0.001, 0.1, 1e-4)
    assert dt.min() >= lo * 0.999 and dt.max() <= hi * 1.001
    assert dt.max() > 5 * dt.min()
    assert (np.asarray(mamba["d_skip"]) == 1).all()
    bias = np.asarray(mamba["conv"]["bias"])
    assert bias.std() > 0.1 and np.abs(bias).max() <= 0.5
    u = np.random.default_rng(0).standard_normal((512, 64)).astype("f4")
    proj = u @ np.asarray(mamba["in_proj"]["kernel"][0])
    assert abs(proj[:, :64].std() - sublayers.MAMBA_GATE_STD) < 0.03
    # x, B, C at the in-projection's own deviation (0.02 x sqrt(64) here,
    # ~1 at the published 2,688), z drawn narrower
    assert abs(proj[:, 64:192].std() - 0.16) < 0.02


def test_a_relu2_expert_hands_its_tokens_no_common_vector(model):
    """``relu(.)^2`` gives every hidden unit a positive mean; the
    down-projections are drawn with each output column summing to zero over
    the hidden units, so that mean maps to nothing: what the routed and the
    shared experts give a batch of tokens has (nearly) no vector common to
    them all, where a plain draw's has a sixth of its energy in one."""
    cfg, _ = model
    exp = init_model_params(cfg, jax.random.PRNGKey(0))["mixers"]["experts"]
    for w in (exp["experts"]["fc2"]["kernel"], exp["shared"]["fc2"]["kernel"]):
        assert float(jnp.abs(w.sum(axis=-2)).max()) < 1e-6
        assert float(w.std()) > 1e-3
    shared = jax.tree.map(lambda a: a[0], exp["shared"])
    u = jax.random.normal(jax.random.PRNGKey(1), (4096, 64))
    act = jnp.square(jax.nn.relu(u @ shared["fc1"]["kernel"]))

    def common_share(out):
        return float((out.mean(0) ** 2).sum() * out.shape[0] / (out ** 2).sum())

    assert common_share(act @ shared["fc2"]["kernel"]) < 0.01
    plain = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    assert common_share(act @ plain) > 0.08


# ---- the three forms -------------------------------------------------------

def _rows(seed, bt, s, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    a_log = jnp.log(jax.random.uniform(k[0], (h,), minval=1.0, maxval=16.0))
    dt, ld = mb.discretize(jax.random.normal(k[1], (bt, s, h)),
                           jnp.full((h,), -3.0), a_log)
    return (jax.random.normal(k[2], (bt, s, h, p)), dt, ld,
            jax.random.normal(k[3], (bt, s, g, n)),
            jax.random.normal(k[4], (bt, s, g, n)))


@pytest.mark.parametrize("drawn", [False, True], ids=["zero", "drawn"])
@pytest.mark.parametrize("s,chunk", [(1, 64), (7, 16), (64, 64), (150, 64),
                                     (300, 128)])
def test_recurrent_and_chunked_forms_agree(s, chunk, drawn):
    """From a zero and from a drawn state, across chunk boundaries (150 =
    2 x 64 + 22; 300 = 2 x 128 + 44) and for a run shorter than a chunk,
    with 2 groups of B and C."""
    assert mb.CHUNK == 128
    rows = _rows(s, 2, s)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 4, 8)) \
        if drawn else jnp.zeros((2, 16, 4, 8))
    want, last = mb.mamba_recurrent(*rows, s0=s0)
    state, got = s0, []
    for lo in range(0, s, chunk):
        y, state = mb.mamba_chunk(state, *(t[:, lo:lo + chunk] for t in rows))
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, rtol=0,
                               atol=2e-4)
    np.testing.assert_allclose(state, last, rtol=0, atol=2e-4)
    y, state = mb.mamba_chunked(*rows, s0=s0, chunk=chunk)
    np.testing.assert_allclose(y, want, rtol=0, atol=2e-4)
    np.testing.assert_allclose(state, last, rtol=0, atol=2e-4)


def test_a_head_reads_its_own_groups_b_and_c():
    """Head ``h`` of 4 in 2 groups reads group ``h // 2``: changing group
    1's B and C moves heads 2 and 3 and leaves heads 0 and 1 their bits."""
    x, dt, ld, b, c = _rows(1, 1, 12)
    y0, _ = mb.mamba_recurrent(x, dt, ld, b, c)
    y1, _ = mb.mamba_recurrent(x, dt, ld, b.at[:, :, 1].multiply(2.0),
                               c.at[:, :, 1].add(1.0))
    np.testing.assert_array_equal(y0[:, :, :2], y1[:, :, :2])
    assert float(jnp.abs(y0[:, :, 2:] - y1[:, :, 2:]).max()) > 0.1


def test_the_grouped_norm_is_not_the_whole_width_one():
    """``RMSNorm(y SiLU(z))`` over each group's values (512 of 4,096 at the
    published widths), against the reference's and against the norm over
    the whole width, from which it must differ."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    y = jax.random.normal(k[0], (5, 64)) * jnp.repeat(
        jnp.asarray([0.5, 1.0, 3.0, 10.0]), 16)    # groups of unequal size
    z, w = jax.random.normal(k[1], (5, 64)), 1 + jax.random.normal(k[2], (64,))
    got = mb.gated_group_norm(y, z, w, 4, 1e-5)
    np.testing.assert_allclose(got, ref.gated_norm(y, z, w, 4, 1e-5),
                               rtol=1e-6, atol=1e-6)
    whole = mb.gated_group_norm(y, z, w, 1, 1e-5)
    assert float(jnp.abs(got - whole).max()) > 1.0
    blocks = (got / w).reshape(5, 4, 16)
    np.testing.assert_allclose(jnp.mean(blocks ** 2, -1), 1.0, atol=1e-3)


# a tick: (slot, first position, rows) runs in order; slot 0 is a dead row
# (the kernel against these: tests/test_mamba_kernel.py)
T = mb.SWEEP_TILE
TICKS = {
    "decode rows, a prompt run from 0, a dead row, a run that goes on":
        [(2, 10, 1), (3, 0, 20), (0, 0, 1), (1, 7, 5)],
    "every row dead": [(0, 0, 6)],
    "a run of every row, fresh": [(4, 0, 19)],
    "decode rows only": [(s, 3 * s, 1) for s in (4, 2, 3, 1)],
    "a reused slot starts from zero whatever it held": [(1, 0, 3), (2, 5, 2)],
    # what the sweep's tiles meet (T rows of the tick's row axis a tile) and
    # a walk of the rows never told apart
    "a run of a tile's rows": [(1, 4, T)],
    "a run of a tile's rows and one": [(2, 0, T + 1)],
    "a run of two tiles' rows and three, from a tile's second row":
        [(4, 7, 1), (3, 9, 2 * T + 3)],
    "two runs whose boundary falls inside a tile (the cell's 24 then 40)":
        [(1, 100, 24), (2, 0, 40)],
    "a run shorter than a tile after decode rows":
        [(4, 3, 1), (2, 8, 1), (1, 30, 5)],
    "a fresh run beside one that goes on in one tile":
        [(3, 0, 10), (1, 17, 12)],
    "a dead row between two runs": [(2, 6, 9), (0, 0, 1), (4, 0, 11)],
}


def _tick_rows(name, h=4, p=8, g=2, n=16):
    slots = np.concatenate([[s] * r for s, _, r in TICKS[name]])
    pos = np.concatenate([np.arange(a, a + r) for _, a, r in TICKS[name]])
    rows = _rows(len(slots), 1, len(slots), h, p, g, n)
    return (*(t[0] for t in rows), jnp.asarray(slots, jnp.int32),
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("name", list(TICKS))
def test_the_ticks_form_is_the_recurrence_a_run(name):
    """Every run reads its slot's state once and leaves the state the
    recurrence leaves; a run at position 0 starts from zero whatever the
    slot held; a dead row and every other slot and layer keep their bits."""
    x, dt, ld, b, c, slots, pos = _tick_rows(name)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 16, 4 * 8))
    y, new = mb.mamba_tick(x, dt, ld, b, c, pool, slots, pos, layer=1)
    lo, touched = 0, set()
    for slot, start, r in TICKS[name]:
        at = slice(lo, lo + r)
        lo += r
        if slot == 0:
            assert not np.asarray(y[at]).any()
            continue
        s0 = jnp.zeros((1, 16, 4, 8)) if start == 0 \
            else pool[1, slot].reshape(1, 16, 4, 8)
        want, last = mb.mamba_recurrent(
            *(t[None, at] for t in (x, dt, ld, b, c)), s0=s0)
        np.testing.assert_allclose(y[at], want[0], rtol=0, atol=2e-4)
        np.testing.assert_allclose(new[1, slot].reshape(16, 4, 8), last[0],
                                   rtol=0, atol=2e-4)
        touched.add(slot)
    rest = [s for s in range(1, 5) if s not in touched]
    np.testing.assert_array_equal(new[1, rest], pool[1, rest])
    np.testing.assert_array_equal(new[0], pool[0])


# ---- the experts: a held share of a relu^2 layer ---------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: ONE expert layer of 16
    experts, each of 8 chips holding 2 of them and the router's 16 outputs
    and top-4.  The 8 chips' parts of the layer's output, the shared expert
    (which every chip computes alike) counted ONCE, add up to what the
    uncut reference gives for the whole layer."""
    kw = dict(sublayer_pattern="E", num_experts=16, moe_router_topk=4)
    whole_cfg = nemotron_cfg(**kw)
    p = jax.tree.map(lambda a: a[0], init_model_params(
        whole_cfg, jax.random.PRNGKey(4))["mixers"]["experts"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 37, 64))
    model = {**MODEL, "hybrid_override_pattern": "E", "n_routed_experts": 16,
             "num_experts_per_tok": 4}
    stack = jax.tree.map(lambda a: a[None], p)
    want = ref.experts(stack, x, model, 0)
    program, _ = moe_mod.moe_sublayer(whole_cfg, p, x)
    np.testing.assert_allclose(program, want, rtol=0, atol=2e-5)

    no_shared = {k: v for k, v in p.items() if k != "shared"}
    shared = ref.experts(stack, x, {**model, "n_shared_experts": 0}, 0)
    shared = want - shared                    # the shared expert alone
    total = shared
    for chip in range(8):
        cfg = nemotron_cfg(**kw, moe_experts_held=2,
                           moe_first_held_expert=2 * chip,
                           moe_capacity_factor=8.0)
        held = {**no_shared, "experts": jax.tree.map(
            lambda a: a[2 * chip:2 * chip + 2], p["experts"])}
        part, aux = moe_mod.moe_sublayer(cfg, held, x)
        assert float(aux[5]) == 0              # no held assignment dropped
        # the reference given the same share: the same part
        ref_part = ref.experts(
            jax.tree.map(lambda a: a[None], {**held, "shared": p["shared"]}),
            x, {**model, "n_shared_experts": 0,
                "first_held_expert": 2 * chip}, 0)
        np.testing.assert_allclose(part, ref_part, rtol=0, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-5)


@pytest.mark.parametrize("width", [32, 128])
def test_an_ungated_experts_up_projection_lies_ffn_by_hidden(width):
    """An expert that is not gated keeps its ``fc1`` as ``[E, ffn, h]``, on
    the 128-lane grid and off it (the published 1,856; the tests' 32): one
    layout, which the dropless path and the reference read."""
    cfg = nemotron_cfg(sublayer_pattern="E", moe_ffn_hidden_size=width,
                       ffn_hidden_size=width)
    p = jax.tree.map(lambda a: a[0], init_model_params(
        cfg, jax.random.PRNGKey(4))["mixers"]["experts"])
    assert p["experts"]["fc1"]["kernel"].shape == (8, width, 64)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 21, 64))
    want = ref.experts(
        jax.tree.map(lambda a: a[None], p), x,
        {**MODEL, "moe_intermediate_size": width}, 0)
    got, _ = moe_mod.moe_sublayer(cfg, p, x)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_grouped_matmul_transposed_and_its_gradients():
    """``grouped_matmul(..., transposed=True)`` on ``W^T`` is
    ``grouped_matmul`` on ``W``, and so are both gradients, the weights' in
    the layout the weights lie in."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    rows = jax.random.normal(k1, (24, 16))
    w = jax.random.normal(k2, (3, 16, 40))
    counts = jnp.asarray([5, 0, 17], jnp.int32)
    cot = jax.random.normal(k3, (24, 40)) * (jnp.arange(24) < 22)[:, None]

    def plain(r, w):
        return (moe_mod.grouped_matmul(r, w, counts) * cot).sum()

    def turned(r, wt):
        return (moe_mod.grouped_matmul(
            r, wt, counts, transposed=True) * cot).sum()

    wt = w.swapaxes(-1, -2)
    np.testing.assert_allclose(turned(rows, wt), plain(rows, w), rtol=1e-5)
    (dr, dw), (dr_t, dw_t) = (jax.grad(f, (0, 1))(rows, a)
                              for f, a in ((plain, w), (turned, wt)))
    np.testing.assert_allclose(dr_t, dr, rtol=0, atol=1e-4)
    np.testing.assert_allclose(dw_t, dw.swapaxes(-1, -2), rtol=0, atol=1e-4)


@pytest.mark.parametrize("dim, cap, tile", [
    (2048, 2048, 2048), (7168, 2048, 1792), (2688, 2048, 896),   # as before
    (1856, 2048, 1920), (1856, 1664, 1664), (2688, 384, 384)])
def test_a_grouped_gemms_tile_at_a_width_off_the_lane_grid(dim, cap, tile):
    """On the 128 grid the tile is what it was (the whole width, else its
    largest divisor on the grid under the cap); off it (1,856 = 14.5 x 128)
    no such divisor exists and the tile is the largest multiple of 128
    under the cap that no more than covers the width: the kernel cuts or
    masks the last one."""
    assert moe_mod._tile(dim, cap) == tile
    assert tile % 128 == 0 and tile - dim < 128


def test_what_an_ungated_experts_layout_means_elsewhere(monkeypatch):
    """tp shards the ffn axis of ``[E, ffn, h]``, the second-last; the
    capacity path (what ep > 1 keeps) reads it as the dropless path does;
    weight int8 takes a kernel's second-last axis for its input, so it is
    refused for such experts in a sentence."""
    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu.parallel.tp import param_partition_specs

    # a uniform stack of ungated experts (tp is refused for a hybrid)
    cfg = make_config(
        "gpt", num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=VOCAB, seq_length=32, max_position_embeddings=64,
        params_dtype="float32", use_flash_attn=False, num_experts=4,
        moe_router_topk=2, ffn_hidden_size=32)
    assert cfg.model.glu_activation is None
    specs = param_partition_specs(jax.eval_shape(
        lambda: init_model_params(cfg, jax.random.PRNGKey(0))))
    experts = specs["layers"]["moe"]["experts"]
    assert experts["fc1"]["kernel"] == P("pp", "ep", "tp", None)
    assert experts["fc2"]["kernel"] == P("pp", "ep", "tp", None)
    roomy = make_config(
        "gpt", num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=VOCAB, seq_length=32, max_position_embeddings=64,
        params_dtype="float32", use_flash_attn=False, num_experts=4,
        moe_router_topk=2, ffn_hidden_size=32, moe_capacity_factor=4.0)
    p = jax.tree.map(lambda a: a[0], init_model_params(
        roomy, jax.random.PRNGKey(1))["layers"]["moe"])
    assert p["experts"]["fc1"]["kernel"].shape == (4, 32, 64)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    dropless, _ = moe_mod.moe_sublayer(roomy, p, x)
    monkeypatch.setattr(moe_mod, "use_dropless", lambda cfg: False)
    padded, _ = moe_mod.moe_sublayer(roomy, p, x)      # no assignment dropped
    np.testing.assert_allclose(padded, dropless, rtol=0, atol=2e-5)
    with pytest.raises(AssertionError, match="experts that are not gated"):
        nemotron_cfg(sublayer_pattern="E", int8_weights=True)


# ---- the model against the reference ---------------------------------------

def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jnp.asarray(prompts(150, 150, seed=1), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, MODEL)
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)


def test_dense_forward_of_a_held_share_matches_reference():
    """The chip's share through the whole stack: the router at 8 outputs,
    experts 4-5 held, the absent experts' terms left out in program and
    reference alike."""
    cfg = nemotron_cfg(moe_experts_held=2, moe_first_held_expert=4,
                       moe_capacity_factor=4.0)
    params = _drawn_norms(init_model_params(cfg, jax.random.PRNGKey(1)))
    assert params["mixers"]["experts"]["experts"]["fc2"]["kernel"].shape == (
        3, 2, 32, 64)
    tokens = jnp.asarray(prompts(60, seed=2), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, {**MODEL, "first_held_expert": 4})
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)
    other = ref.logits(params, tokens, {**MODEL, "first_held_expert": 0})
    assert float(jnp.abs(jax.nn.log_softmax(other)
                         - jax.nn.log_softmax(want)).max()) > 100 * ATOL


def test_the_residual_stream_is_the_activations_dtype(monkeypatch):
    """The published ``residual_in_fp32`` is false: the stream a layer's norm
    reads and its mixer adds to is bfloat16 where the activations are,
    rounded after every layer's add."""
    cfg = nemotron_cfg(params_dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          init_model_params(cfg, jax.random.PRNGKey(0)))
    seen = {"norm": set(), "mixer": set()}
    norm, mamba = sublayers.norm, sublayers.mamba_sublayer

    def spy_norm(x, *a, **kw):
        seen["norm"].add(x.dtype)
        return norm(x, *a, **kw)

    def spy_mamba(cfg_, p, x, **kw):
        seen["mixer"].add(x.dtype)
        return mamba(cfg_, p, x, **kw)

    monkeypatch.setattr(sublayers, "norm", spy_norm)
    monkeypatch.setattr(sublayers, "mamba_sublayer", spy_mamba)
    tokens = jnp.asarray(prompts(20, seed=5), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    assert seen == {"norm": {jnp.dtype("bfloat16")},
                    "mixer": {jnp.dtype("bfloat16")}}
    assert logits.dtype == jnp.bfloat16 or logits.dtype == jnp.float32
    want = ref.logits(params, tokens, MODEL)
    diff = jnp.abs(jax.nn.log_softmax(logits.astype(jnp.float32))
                   - jax.nn.log_softmax(want))
    assert float(diff.mean()) < 0.05        # bfloat16 GEMMs, eight layers


FAULTS = {
    "decay_ignored": ("decay", lambda a_log, dt: 1.0 + 0.0 * dt),
    "dt_without_softplus": ("step_size", lambda raw, bias: raw + bias),
    "every_head_reads_group_0": (
        "group_of", lambda heads, groups: jnp.zeros((heads,), jnp.int32)),
    "norm_over_the_whole_width": ("norm_groups", lambda model: 1),
    "conv_bias_dropped": ("conv_bias", lambda bias: 0.0 * bias),
    "relu_for_relu_squared": ("expert_activation", jax.nn.relu),
    "scaling_factor_one": ("routed_scale", lambda model: 1.0),
    "shared_expert_left_out": ("shared_weight", lambda model: 0.0),
    "rotary_applied": ("rotates_qk", lambda model: True),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_fault_fails_the_comparison(model, fault, monkeypatch):
    """Every choice the reference exposes is ONE function, and turning it
    moves the comparison far outside what the honest program reads: a fault
    of that kind in the program would show."""
    cfg, params = model
    if fault == "rotary_applied":
        # q and k entries of deviation 0.02 x sqrt(64) give scores of 0.03:
        # a softmax uniform whatever its positions.  Weigh them as the
        # published widths do (0.02 x sqrt(2688) an entry, 128 a head:
        # scores of deviation ~1.1), and the out-projection with them
        params = jax.tree.map(lambda a: a, params)
        att = params["mixers"]["attention"]
        att["qkv"]["kernel"] = att["qkv"]["kernel"] * 6.0
        att["dense"]["kernel"] = att["dense"]["kernel"] * 4.0
    tokens = jnp.asarray(prompts(90, seed=11), jnp.int32)
    got = jax.nn.log_softmax(model_forward(cfg, params, tokens)[0])
    honest = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    assert float(jnp.abs(got - honest).max()) < ATOL
    name, other = FAULTS[fault]
    monkeypatch.setattr(ref, name, other)
    faulty = jax.nn.log_softmax(ref.logits(params, tokens, MODEL))
    diff = np.abs(np.asarray(got - faulty))
    assert not np.isfinite(diff).all() or diff.max() > 100 * ATOL, fault


# ---- through the engine: pages and slots -----------------------------------

def _assert_idle(eng):
    assert isinstance(eng.pool, PagedKVPool) and not eng.pool.state
    assert isinstance(eng.spool, StatePool) and eng.cache is None
    assert eng.spool.num_free == eng.max_slots
    assert eng.pool.num_free == eng.pool.num_pages - 1
    assert not eng.pool.refcounts.any() and not eng.spool.refcounts.any()
    assert [cls.width for cls in eng._classes] == [eng.pages_per_seq, 1]
    assert_memory_idle(eng)


def test_engine_matches_reference_through_pages_and_slots(model):
    """Prefill in chunks, then decode, two requests of unequal length in
    the same ticks: the two attention layers from K/V pages, the three
    Mamba layers from the state slot, the expert layers keeping nothing;
    then MORE requests than slots, so that slots and pages change hands,
    one of them a one-token prompt whose first row is a decode row at
    position 0."""
    cfg, params = model
    eng = engine(cfg, params, max_slots=2)
    assert eng.pool.kv.shape == (2, 65, PAGE, 2 * 2 * 16)   # TWO K/V layers
    assert not eng.pool.latent
    assert eng.spool.kv.s.shape == (3, 3, 16, 8 * 8)        # THREE Mamba ones
    assert eng.spool.kv.conv.shape == (3 * 3, 3 * 128)
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


def test_engine_serves_a_held_share(model):
    """The chip's share through the engine: the held-expert counters ride
    the tick's fetch."""
    cfg = nemotron_cfg(moe_experts_held=2, moe_first_held_expert=4,
                       moe_capacity_factor=4.0)
    params = init_model_params(cfg, jax.random.PRNGKey(1))
    eng = engine(cfg, params, max_slots=2)
    reqs = [eng.submit(p, 8, top_k=1, termination_id=NEVER)
            for p in prompts(40, 9, seed=3)]
    eng.run_until_idle()
    for req in reqs:
        check(req, params, {**MODEL, "first_held_expert": 4})
    assert eng.moe_held_experts_touched > 0


def test_two_requests_through_one_slot_in_turn(model):
    """One slot: the second request takes the state slot the first left,
    whatever it held, and starts from zero at its position 0."""
    cfg, params = model
    eng = engine(cfg, params, max_slots=1)
    reqs = [eng.submit(p, 8, top_k=1, termination_id=NEVER)
            for p in prompts(45, 30, seed=7)]
    eng.run_until_idle()
    assert eng.spool.num_pages == 2          # the null slot and the one
    for req in reqs:
        check(req, params)
    _assert_idle(eng)


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
    assert eng.state_recomputed_tokens == len(p) + done - 1
    _assert_idle(eng)


def test_metrics_count_the_state_the_pages_and_the_experts(model):
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
    # the Mamba sweep takes a run a tile at a time: a tick's 16 prompt rows
    # lie in one tile of its row axis, so a run is one pass, as a touch is
    assert got["state_steps"] == got["state_touches"]
    assert got["state_resets"] == 2          # one run at position 0 each
    assert got["paged_rows"] == got["state_rows"]
    assert got["moe_assignments"] > 0        # the three expert layers' rows
    # the two classes' bytes apart: the state slots and the K/V pages
    state = 3 * 5 * (16 * 8 * 8 + 3 * 128) * 4
    pages = 2 * eng.pool.num_pages * PAGE * 2 * 2 * 16 * 4
    assert reg.gauge("mlt_engine_state_pool_bytes").value == \
        eng.spool.kv_pool_bytes() == state
    assert reg.gauge("mlt_engine_kv_pool_bytes").value == \
        eng.pool.kv_pool_bytes() == pages


def test_pool_classes_memory_kind_and_bytes_at_published_widths():
    cfg = make_config("nemotron_h-3-nano-30b-a3b", params_dtype="bfloat16")
    page, state = pool_classes(cfg)
    assert memory_kind(cfg) == "hybrid" == memory_kind(nemotron_cfg())
    assert (page.name, page.state) == ("full", False)
    assert page.places == tuple(i for i, c in enumerate(PUBLISHED) if c == "*")
    assert state.name == "state" and state.state
    assert state.places == tuple(
        i for i, c in enumerate(PUBLISHED) if c == "M")
    assert (page.layers(cfg), state.layers(cfg)) == (6, 23)
    # abstract pools: 2 MiB of state and 72 KiB of tail a layer and slot,
    # 1 KiB of keys and values a token and layer
    spool = jax.eval_shape(lambda: StatePool(cfg, 1, 16, layers=23).kv)
    assert spool.s.shape == (23, 2, 128, 4096) and spool.s.dtype == jnp.float32
    assert spool.s.size // (23 * 2) * 4 == 2 << 20
    assert spool.conv.shape == (23 * 2, 3 * 6144)
    pool = jax.eval_shape(lambda: PagedKVPool(
        cfg, 3, 16, layers=6, page_class="full").kv)
    assert pool.shape == (6, 3, 16, 2 * 2 * 128) and pool.dtype == jnp.bfloat16
    assert pool.size // (6 * 3 * 16) * 2 == 1024
    # a pattern of Mamba layers alone has the state class and no other
    alone = nemotron_cfg(sublayer_pattern="MM", num_experts=None,
                         moe_shared_experts=0)
    assert [c.state for c in pool_classes(alone)] == [True]


def test_the_names_the_cells_readers_match():
    """``ssm_share.nemotron`` reads the scope ``mamba``,
    ``ssm_roofline.nemotron`` the kernel ``mamba_sweep``,
    ``expert_gemm_roofline.nemotron`` the scope ``moe/expert_gemm``: a
    reader that matches nothing reports nothing and guards nothing."""
    cfg = nemotron_cfg()
    params = jax.eval_shape(lambda: init_model_params(
        cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    text = jax.jit(lambda p, t: model_forward(cfg, p, t)[0]).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("mamba/conv", "mamba/sweep", "mamba/gated_norm",
                  "moe/expert_gemm", "attention/global"):
        assert scope in text, scope
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    readers = os.path.join(root, "benchmark", "layer_metrics")
    for name, needle in (
            ("ssm_share.nemotron", '"mamba"'),
            ("ssm_roofline.nemotron", '"mamba_sweep"'),
            ("expert_gemm_roofline.nemotron", '"/moe/expert_gemm/"')):
        with open(os.path.join(readers, name + ".py")) as f:
            assert needle in f.read(), name


# ---- what the hybrid does not carry yet ------------------------------------

def _mesh(**kw):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**kw, data_parallel_size=1, devices=jax.devices()[:2])


REFUSED = [
    (dict(kv_dtype="int8"), "--kv_dtype int8"),
    (dict(kv_dtype="fp8"), "--kv_dtype fp8"),
    (dict(mesh="tp"), "tensor-parallel serving (tp 2)"),
    (dict(mesh="pp"), "pipeline-parallel serving (pp 2)"),
    (dict(draft=True), "--spec_k"),
    (dict(handoff=True), "cross-replica KV handoff"),
    (dict(log_probs=True), "return_log_probs"),
]


@pytest.mark.parametrize("kw,sentence", REFUSED,
                         ids=[s.split()[-1] if s.startswith("--kv")
                              else s.split()[0] for _, s in REFUSED])
def test_refuse_unserved_says_why(model, kw, sentence):
    """The ``hybrid`` rows' sentences, true of K/V pages too: the pages are
    named as what they hold."""
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
    assert (f"a hybrid stack (sublayer_pattern {PATTERN}) keeps a recurrent "
            "state a sequence for its linear layers beside pages of keys "
            "and values") in text
    assert "latent row has" not in text


def test_a_held_share_is_refused_on_a_mesh():
    cfg = nemotron_cfg(moe_experts_held=2, moe_capacity_factor=4.0)
    with pytest.raises(ValueError, match="moe_experts_held 2 of 8 is one "
                                         "chip's share"):
        refuse_unserved(cfg, mesh=_mesh(tensor_model_parallel_size=2))


def test_engine_refuses_at_start_up_and_at_the_request(model):
    cfg, params = model
    with pytest.raises(ValueError, match="--kv_dtype fp8"):
        engine(cfg, params, kv_dtype="fp8")
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="return_log_probs"):
        eng.submit(prompts(12)[0], 4, return_log_probs=True)


def test_the_stack_takes_the_dense_forward_and_the_tick_alone(model):
    """No dense incremental cache, packed segments or dropout: said in a
    sentence, not computed wrongly."""
    cfg, params = model
    tokens = jnp.asarray(prompts(8), jnp.int32)
    with pytest.raises(AssertionError, match="a stack of one-sublayer "
                                             "layers runs the dense forward"):
        model_forward(cfg, params, tokens,
                      segment_ids=jnp.zeros((1, 8), jnp.int32))
