"""ByteDance Ouro (``ouro``: Ouro-2.6B, a LoopLM) on the normal serving path,
at tiny widths on the CPU: a dense sandwich-normed stack run ``loop_steps``
times over the SAME weights, the final norm and the exit gate after every
pass, every pass's keys and values on page slots of its own.  Everything is
compared with the plain reference (``benchmark/reference/ouro_block.py``) on
the same weights, with the norm scales and the gate DRAWN: the dense forward
with its ``logits``, the engine's emitted log-probabilities with its full
forward, the pool's slots with the keys each pass computed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import ouro_block as ref
from megatron_llm_tpu.config.arguments import MODEL_SIZES, parse_args
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.pools import (
    FEATURES,
    KEEPS,
    NOT_CARRIED,
    memory_kind,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models.language_model import exit_pdf, model_forward
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.training_step import make_train_step
from tests.parity import (
    assert_memory,
    assert_memory_idle,
    dense_greedy,
    held_pages,
)

ATOL = 2e-4
VOCAB, PAGE, NEVER = 96, 8, 10 ** 9
LAYERS = 2
WIDTHS = dict(
    num_layers=LAYERS, hidden_size=64, num_attention_heads=4,
    num_attention_heads_kv=4, kv_channels=16, ffn_hidden_size=96,
    vocab_size=VOCAB, max_position_embeddings=256, seq_length=256,
    params_dtype="float32", use_flash_attn=False)
LOOPS = (1, 2, 4)


def ouro_cfg(loops=4, **kw):
    return make_config("ouro", **{**WIDTHS, "loop_steps": loops, **kw})


def ref_model(loops, threshold=1.0):
    """The same sizes under the published config's names."""
    return dict(
        num_hidden_layers=LAYERS, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, rms_norm_eps=1e-6,
        rope_theta=1000000.0, vocab_size=VOCAB, total_ut_steps=loops,
        early_exit_threshold=threshold)


def drawn(cfg, seed=0):
    """The initialiser leaves every norm's scale at 1 and the gate's bias at
    0: draw them (and a gate wide enough that the exit masses spread), so
    that a norm or a bias the program forgot would show."""
    params = init_model_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)
    out = jax.tree.map(lambda a: a, params)
    lay = out["layers"]
    for i, node in enumerate((lay["input_norm"], lay["post_norm"],
                              lay["attn_out_norm"], lay["mlp_out_norm"],
                              out["final_norm"])):
        node["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), node["scale"].shape)
    if "exit_gate" in out:
        out["exit_gate"] = {
            "kernel": 0.2 * jax.random.normal(
                jax.random.fold_in(key, 9), (cfg.model.hidden_size, 1)),
            "bias": jnp.asarray([-0.4], jnp.float32)}
    out["lm_head"]["kernel"] = out["lm_head"]["kernel"] * 8.0
    return out


def for_reference(params):
    """The reference reads a gate whatever the pass count; a model of one
    pass has none, and its one exit mass is 1 whatever the gate says."""
    if "exit_gate" in params:
        return params
    return {**params, "exit_gate": {"kernel": jnp.zeros((64, 1)),
                                    "bias": jnp.zeros((1,))}}


@pytest.fixture(scope="module", params=LOOPS, ids=[f"loops{t}" for t in LOOPS])
def model(request):
    cfg = ouro_cfg(request.param)
    return cfg, drawn(cfg), request.param


def engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, **{**dict(max_slots=4, page_size=PAGE, max_seq=256,
                               prefill_chunk=16), **kw})


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, VOCAB, n)] for n in lengths]


def ask(eng, prompt, n, **kw):
    return eng.submit(prompt, n, top_k=1, termination_id=NEVER, **kw)


def check(req, params, loops):
    """The log-probability the engine reported for every token it emitted
    against the reference's FULL forward over prompt + emitted tokens."""
    tokens, lps = req.result(timeout=120)
    n = len(tokens) - len(req.prompt)
    seq = jnp.asarray([tokens], jnp.int32)
    logits = ref.logits(for_reference(params), seq, ref_model(loops))
    want = ref_common.token_log_probs(logits, seq)[0, len(req.prompt) - 1:]
    assert n == len(lps) == want.shape[0]
    np.testing.assert_allclose(lps, want, rtol=0, atol=ATOL)


def _assert_idle(eng):
    assert all(r is None for r in eng._slots) and not eng._inflight
    assert_memory_idle(eng)


# ---- the family ------------------------------------------------------------

def test_family_preset_and_parameter_tree():
    cfg = ouro_cfg()
    m = cfg.model
    assert cfg.model_name == "ouro" and memory_kind(cfg) == "loop"
    assert m.loop_steps == 4 and not hasattr(m, "early_exit_threshold")
    assert m.post_sublayer_norms and not m.zero_centered_gated_norm
    assert not m.tie_embed_logits and not m.use_bias and m.rope_theta == 1e6
    assert m.depth == LAYERS and m.cache_layer_slots == 4 * LAYERS
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    # L layers of weights, never loops x L; four norms a layer; the gate
    assert params["layers"]["attention"]["qkv"]["kernel"].shape[0] == LAYERS
    assert set(params["layers"]) == {
        "input_norm", "attention", "attn_out_norm", "post_norm", "mlp",
        "mlp_out_norm"}
    assert params["exit_gate"]["kernel"].shape == (64, 1)
    assert params["exit_gate"]["bias"].shape == (1,)
    # one pass: the tree has no gate (and the forward no loop, below)
    once = init_model_params(ouro_cfg(1), jax.random.PRNGKey(0))
    assert "exit_gate" not in once
    assert memory_kind(ouro_cfg(1)) == "paged"
    with pytest.raises(ValueError, match="plain RMSNorm"):
        ouro_cfg(post_sublayer_norms=False)
    with pytest.raises(AssertionError, match="MoE is supported for"):
        ouro_cfg(num_experts=4, moe_router_topk=2, moe_ffn_hidden_size=32)
    with pytest.raises(AssertionError, match="ONE scanned stack"):
        ouro_cfg(sliding_window_layout=(1, 0), sliding_window_size=8)


def test_the_preset_is_the_published_model_whole():
    """Every published size from the preset, no flag naming a width; the
    parameter sum and the cache's bytes a token from ``jax.eval_shape``."""
    cfg = parse_args(["--model_name", "ouro-2.6b", "--tokenizer_type",
                      "NullTokenizer", "--params_dtype", "bfloat16"],
                     n_devices=1)
    m = cfg.model
    assert cfg.model_name == "ouro"
    assert (m.num_layers, m.hidden_size, m.num_attention_heads,
            m.num_attention_heads_kv, m.kv_channels, m.ffn_hidden_size,
            m.vocab_size, m.max_position_embeddings, m.loop_steps) == (
        48, 2048, 16, 16, 128, 5632, 49152, 65536, 4)
    assert MODEL_SIZES["ouro-2.6b"]["loop_steps"] == 4
    tree = jax.eval_shape(lambda: init_model_params(cfg, jax.random.PRNGKey(0)))
    sizes = jax.tree.map(lambda a: int(np.prod(a.shape)), tree)
    layer = sum(jax.tree.leaves(sizes["layers"])) // 48
    assert layer == 51_388_416
    # 48 layers + embedding and head (49,152 = 384 x 128: no padding) + the
    # final norm (2,048) + the gate and its bias (2,049)
    assert sum(jax.tree.leaves(sizes)) == 48 * layer + 2 * 49152 * 2048 + 4097
    assert sum(jax.tree.leaves(sizes)) == 2_667_974_657
    from megatron_llm_tpu.ops import kv_quant

    pool = jax.eval_shape(lambda: kv_quant.make_kv_pool(
        m.cache_layer_slots, 3, 16, 16, 128, "bf16", jnp.bfloat16))
    assert pool.shape[0] == 192
    page = int(np.prod(pool.shape)) * 2 // 3
    assert page == 16 * 1_572_864            # 24 MiB a page, 1.5 MiB a token


# ---- the dense forward -----------------------------------------------------

def test_dense_forward_is_the_reference(model):
    cfg, params, loops = model
    tokens = jnp.asarray(prompts(37, 37, seed=1), jnp.int32)
    got, _ = model_forward(cfg, params, tokens)
    want = ref.logits(for_reference(params), tokens, ref_model(loops))
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("loops", (2, 4))
def test_exit_pdf_sums_to_one_and_is_the_references(loops):
    cfg = ouro_cfg(loops)
    params = drawn(cfg)
    tokens = jnp.asarray(prompts(29, seed=2), jnp.int32)
    _, _, (_, pdf) = model_forward(cfg, params, tokens, return_aux=True)
    assert pdf.shape == (1, 29, loops) and pdf.dtype == jnp.float32
    np.testing.assert_allclose(pdf.sum(-1), 1.0, rtol=0, atol=1e-6)
    _, lams = ref.run_passes(params, tokens, ref_model(loops))
    want = jnp.stack(ref.exit_pdf(lams), axis=-1)
    np.testing.assert_allclose(pdf, want, rtol=0, atol=1e-5)
    # the drawn gate spreads the mass: no pass holds next to all of it
    assert float(pdf.mean((0, 1)).max()) < 0.9
    # the reference reads the LAST pass at the published threshold, and an
    # earlier one for some token at a lower one
    last = ref.exit_pass(ref.exit_pdf(lams), ref_model(loops))
    assert int(last.min()) == loops - 1
    early = ref.exit_pass(ref.exit_pdf(lams), ref_model(loops, 0.3))
    assert int(early.min()) < loops - 1


def test_exit_pdf_by_hand():
    lam = jax.nn.sigmoid(jnp.asarray([0.0, 1.0, -1.0, 5.0]))
    got = exit_pdf(jnp.log(lam / (1 - lam))[:, None])[:, 0]
    want = [lam[0], lam[1] * (1 - lam[0]),
            lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-6)


def test_one_pass_traces_with_no_loop_and_no_gate():
    """``loop_steps`` 1 is every other model's forward: the lowered program
    holds no scan over passes (one ``while``: the layer scan) and reads no
    gate; two passes hold the two nested."""
    def lowered(loops):
        cfg = ouro_cfg(loops)
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((1, 8), jnp.int32)
        return jax.jit(lambda p: model_forward(cfg, p, tokens)[0]).lower(
            params).as_text()

    assert lowered(1).count("stablehlo.while") == 1
    assert lowered(2).count("stablehlo.while") == 2     # ONE body a pass
    assert lowered(4).count("stablehlo.while") == 2


def test_dense_incremental_cache_is_the_full_forward(model):
    """``generate_tokens``' cache holds a pair a layer and pass."""
    cfg, params, loops = model
    p, = prompts(21, seed=3)
    caches = gen.init_kv_caches(cfg, 1, 32, jnp.float32)
    assert caches[0].shape[0] == loops * LAYERS
    tokens, lps = dense_greedy(cfg, params, p, 6)
    seq = jnp.asarray([tokens], jnp.int32)
    want = ref_common.token_log_probs(
        ref.logits(for_reference(params), seq, ref_model(loops)), seq)[0]
    np.testing.assert_allclose(lps, want, rtol=0, atol=ATOL)


# ---- the engine ------------------------------------------------------------

def test_engine_prefills_across_a_chunk_boundary_then_decodes(model):
    cfg, params, loops = model
    eng = engine(cfg, params)
    assert eng.pool.kv.shape[0] == loops * LAYERS and eng.wpool is None
    reqs = [ask(eng, p, 12) for p in prompts(37, 16, 5, seed=4)]
    eng.step()
    assert_memory(eng)
    eng.run_until_idle()
    for req in reqs:
        check(req, params, loops)
    _assert_idle(eng)


def test_a_prefix_hit_skips_every_pass_of_the_hit_tokens(model):
    """A page holds a token's rows for ALL passes: the second request
    prefills its own suffix only, and reads as the reference does."""
    cfg, params, loops = model
    eng = engine(cfg, params)
    p, = prompts(45, seed=5)
    first = ask(eng, p, 6)
    eng.run_until_idle()
    before = eng.prefill_tokens_computed
    tail, = prompts(9, seed=6)
    second = ask(eng, p[:40] + tail, 6)
    eng.run_until_idle()
    assert eng.prefix_hit_tokens == 40
    # ONE chunk of prompt rows for the suffix, where the prompt took three
    assert (before, eng.prefill_tokens_computed - before) == (48, 16)
    for req in (first, second):
        check(req, params, loops)
    _assert_idle(eng)


def test_preempted_and_recomputed_matches_the_reference(model):
    cfg, params, loops = model
    eng = engine(cfg, params, prefix_cache=False)
    p, = prompts(30, seed=7)
    req = ask(eng, p, 14)
    while len(req.generated) < 5:
        eng.step()
    assert eng.preempt(req) and req._phase == "queued"
    assert not held_pages(req)
    assert_memory(eng)
    eng.run_until_idle()
    assert req._preemptions == 1
    check(req, params, loops)
    _assert_idle(eng)


def test_return_log_probs_scores_the_prompt_through_every_pass(model):
    cfg, params, loops = model
    eng = engine(cfg, params)
    p, = prompts(27, seed=8)
    req = ask(eng, p, 4, return_log_probs=True)
    eng.run_until_idle()
    tokens, _ = req.result(timeout=120)
    seq = jnp.asarray([tokens], jnp.int32)
    want = ref_common.token_log_probs(
        ref.logits(for_reference(params), seq, ref_model(loops)), seq)[0]
    np.testing.assert_allclose(req.prompt_log_probs, want[:len(p) - 1],
                               rtol=0, atol=ATOL)
    check(req, params, loops)


@pytest.mark.parametrize("loops", (2, 4))
def test_pass_t_writes_its_own_slots_and_no_other(loops):
    """Slot ``t * L + l`` of a sequence's pages holds the keys and values
    pass t (from 0) computed at layer l, for every t and l: read off the
    pool after a prompt and some decode ticks, against the reference's."""
    cfg = ouro_cfg(loops)
    params = drawn(cfg)
    eng = engine(cfg, params, prefix_cache=False)
    p, = prompts(21, seed=9)
    req = ask(eng, p, 8)
    while len(req.generated) < 4:
        eng.step()
    pages = held_pages(req)
    keys, values = eng.pool.logical_kv(pages)       # [slots, n, page, nkv, d]
    seq = p + req.generated
    written = len(seq) - 1          # the last token's K/V is the next tick's
    model = ref_model(loops)
    f32 = ref_common.f32
    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"]["word_embeddings"])[jnp.asarray([seq])]
        for t in range(loops):
            for layer in range(LAYERS):
                x, (k, v) = ref.block(
                    f32(jax.tree.map(lambda a: a[layer], params["layers"])),
                    x, model)
                slot = t * LAYERS + layer
                for got, want in ((keys, k), (values, v)):
                    flat = got[slot].reshape(-1, *got.shape[-2:])[:written]
                    np.testing.assert_allclose(
                        flat, want[0, :written], rtol=0, atol=ATOL,
                        err_msg=f"pass {t} layer {layer}")
            x = ref_common.rms_norm(x, f32(params["final_norm"]["scale"]),
                                    model["rms_norm_eps"])
    assert keys.shape[0] == loops * LAYERS
    # two passes' slots of one layer differ: nothing is shared or aliased
    assert np.abs(keys[0] - keys[LAYERS]).max() > 1e-3
    eng.run_until_idle()
    check(req, params, loops)


def test_counters_count_the_exit_masses():
    cfg = ouro_cfg(4)
    params = drawn(cfg)
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    read = lambda name, **lb: reg.counter(name, labels=lb or None).value  # noqa: E731
    names = [("mlt_engine_ticked_tokens_total", {})] + [
        ("mlt_engine_loop_exit_mass_total", {"step": str(t)})
        for t in (1, 2, 3, 4)]
    before = [read(n, **lb) for n, lb in names]
    eng = engine(cfg, params)
    reqs = [ask(eng, p, 10) for p in prompts(20, 7, seed=10)]
    eng.run_until_idle()
    got = [read(n, **lb) - b for (n, lb), b in zip(names, before)]
    tokens, *masses = got
    assert tokens == 20
    # every sampled row's distribution sums to one; the drawn gate leaves
    # mass at every pass
    assert sum(masses) == pytest.approx(20, abs=1e-3)
    assert min(masses) > 0.1
    np.testing.assert_allclose(masses, eng.loop_exit_mass, atol=1e-6)
    # ... and is the reference's over the rows that were sampled
    want = np.zeros(4)
    for req in reqs:
        seq = jnp.asarray([req.prompt + req.generated], jnp.int32)
        _, lams = ref.run_passes(params, seq, ref_model(4))
        pdf = np.stack([np.asarray(x) for x in ref.exit_pdf(lams)], -1)[0]
        want += pdf[len(req.prompt) - 1:-1].sum(0)
    np.testing.assert_allclose(masses, want, atol=1e-3)
    # a model that does not loop has no such series
    once = engine(ouro_cfg(1), drawn(ouro_cfg(1)))
    assert once._m_loop_mass is None and once.loop_exit_mass.shape == (1,)


def test_a_looped_expert_stack_reports_its_router_and_its_exit_masses():
    """No model-validity rule follows from how the tick packs its fetch: a
    looped stack WITH experts runs, its router's counts (summed over the
    passes) and its exit masses both riding the one fetch, each to a field
    of its own."""
    cfg = make_config("mixtral", **{
        **WIDTHS, "loop_steps": 2, "num_experts": 4, "moe_router_topk": 2,
        "moe_capacity_factor": 2.0})     # dropless: rows decide nothing
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(prompts(12, seed=3), jnp.int32)
    logits, _, (aux, pdf) = model_forward(cfg, params, tokens,
                                          return_aux=True)
    # 12 rows x top-2 x 2 layers x 2 passes
    assert float(aux[2]) == 12 * 2 * LAYERS * 2 and pdf.shape == (1, 12, 2)
    eng = engine(cfg, params)
    req = ask(eng, [int(t) for t in tokens[0]], 5)
    eng.run_until_idle()
    assert eng.loop_exit_mass.sum() == pytest.approx(5, abs=1e-4)
    assert eng.moe_assignments > 0
    # ... and reports what the dense forward reads at its tokens
    tokens, lps = req.result(timeout=120)
    seq = jnp.asarray([tokens], jnp.int32)
    want = ref_common.token_log_probs(
        model_forward(cfg, params, seq)[0], seq)[0, 11:]
    np.testing.assert_allclose(lps, want, rtol=0, atol=ATOL)


# ---- what is refused -------------------------------------------------------

def _mesh(**axes):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    return build_mesh(**axes, data_parallel_size=1,
                      devices=jax.devices()[:2])


REFUSED = [
    (dict(kv_dtype="int8"), "kv_dtype", "--kv_dtype int8"),
    (dict(kv_dtype="fp8"), "kv_dtype", "--kv_dtype fp8"),
    (dict(mesh="tp"), "tp", "tensor-parallel serving (tp 2)"),
    (dict(mesh="pp"), "pp", "pipeline-parallel serving (pp 2)"),
    (dict(draft=True), "draft", "--spec_k"),
    (dict(handoff=True), "handoff", "cross-replica KV handoff"),
]


@pytest.mark.parametrize("kw,feature,sentence", REFUSED,
                         ids=[f"{f}-{i}" for i, (_, f, _) in enumerate(REFUSED)])
def test_refuse_unserved_says_why(kw, feature, sentence):
    cfg = ouro_cfg()
    kw = dict(kw)
    if kw.get("mesh") == "tp":
        kw["mesh"] = _mesh(tensor_model_parallel_size=2)
    elif kw.get("mesh") == "pp":
        kw["mesh"] = _mesh(pipeline_model_parallel_size=2)
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, **kw)
    text = str(e.value)
    assert sentence in text
    assert NOT_CARRIED["loop", feature].split("{")[0] in text
    assert ("a looped stack (loop_steps 4) runs its layers 4 times over the "
            "same weights") in text
    assert KEEPS["loop"].split("{")[0] in text


def test_an_exit_below_one_is_refused_where_a_config_enters():
    """The program has no ``early_exit_threshold``: it reads the last pass's
    logits, the published 1.0.  A checkpoint's config that says less is
    refused where it enters, with what is missing."""
    from types import SimpleNamespace

    from weights_conversion.hf_to_native import config_from_hf

    sizes = MODEL_SIZES["ouro-2.6b"]
    published = dict(
        num_hidden_layers=48, hidden_size=2048, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, intermediate_size=5632,
        vocab_size=49152, max_position_embeddings=65536, rms_norm_eps=1e-6,
        rope_theta=1000000.0, rope_scaling=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1.0)
    m = config_from_hf(SimpleNamespace(**published), "ouro").model
    assert {k: getattr(m, k) for k in sizes} == sizes
    with pytest.raises(ValueError) as e:
        config_from_hf(SimpleNamespace(
            **{**published, "early_exit_threshold": 0.5}), "ouro")
    text = str(e.value)
    assert "early_exit_threshold 0.5 (below 1)" in text
    assert "K/V policy for the passes a token skipped" in text


def test_the_trainer_and_the_engine_refuse_at_start_up():
    cfg = ouro_cfg()
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="entropy term whose coefficient is "
                                         "no key of the config"):
        make_train_step(cfg)
    make_train_step(ouro_cfg(1))            # one pass trains as ever
    with pytest.raises(ValueError, match="--kv_dtype fp8"):
        engine(cfg, params, kv_dtype="fp8")
    # what the table has no row for is served: prompt scoring (above), the
    # block tick is another family's
    assert ("loop", "log_probs") not in NOT_CARRIED
    assert {f for k, f in NOT_CARRIED if k == "loop"} == (
        set(FEATURES) - {"log_probs"})
    refuse_unserved(cfg, log_probs=True)


# ---- what the PR leaves as it was ------------------------------------------

def test_an_accepted_presets_tick_is_lowered_as_before():
    """A Pallas kernel's payload in a lowered tick names the LINES of its
    callers (tools/tick_digest.py), so an accepted cell's tick is the
    parent's program only while those calls stand where they stood: the
    stack's call in ``model_forward``, the forward's and the tick's in
    ``generation/ragged.py``.  And a model that does not loop lowers with no
    pass loop, no gate and none of the looped stack's scopes."""
    import inspect

    from megatron_llm_tpu.generation import ragged
    from megatron_llm_tpu.models import language_model

    def line_of(module, text):
        lines = inspect.getsource(module).splitlines()
        hits = [i + 1 for i, line in enumerate(lines) if text in line]
        return hits

    assert line_of(language_model,
                   "hidden, new_caches, aux = transformer_forward(") == [318]
    assert line_of(ragged, "logits, pool_kv, aux = model_forward(") == [237]
    assert line_of(ragged, "out, pool_kv, aux = target_forward(") == [402]
    assert line_of(language_model, "def loss_from_batch(") == [357]
    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32

    def lowered(cfg):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        nkv = cfg.model.num_attention_heads_kv
        pool = S((cfg.model.cache_layer_slots, 9, PAGE, 2 * nkv * 16), f32)
        return params, jax.jit(ragged.make_ragged_tick_fn(
            cfg, None, 0, 0)).lower(
            params, pool, S((2, 4), i32), S((2,), i32), S((2,), i32),
            S((2, 2), jnp.uint32), S((2,), i32), S((2,), f32), S((2,), i32),
            S((2,), f32), S((2,), i32), S((2,), jnp.bool_))

    params, plain = lowered(make_config(
        "llama2", **{**WIDTHS, "num_attention_heads_kv": 2}))
    _, once = lowered(ouro_cfg(1))
    _, looped = lowered(ouro_cfg(4))
    assert "exit_gate" not in params
    for out in (plain, once):
        text = out.as_text(debug_info=True)
        assert "attention" in text          # the scopes are in this text
        assert "loop_pass" not in text and "loop_norm_gate" not in text
        assert len(out.out_info) == 5                   # no aux beside them
    loops = [out.as_text().count("stablehlo.while")
             for out in (plain, once, looped)]
    # the layer scan and the sampler's loops; a looped stack ONE more
    assert loops[0] == loops[1] == loops[2] - 1
    assert "loop_pass" in looped.as_text(debug_info=True)
    assert len(looped.out_info) == 6


def test_the_fp8_control_rounds_the_matrices_and_leaves_the_vectors():
    """``tools/serve_faults.py --fp8``, the control the cell's limits lie
    under: every matrix on float8_e4m3's grid under its own scale (a pair of
    ``astype`` there and back is dropped by the chip's compiler: it read 0.0),
    a norm's scale and a bias as given."""
    import ml_dtypes

    from tools.serve_faults import fp8_weights

    cfg = ouro_cfg(2)
    params = drawn(cfg)
    low = fp8_weights(params)
    w = np.asarray(params["layers"]["mlp"]["fc2"]["kernel"], np.float32)
    got = np.asarray(low["layers"]["mlp"]["fc2"]["kernel"], np.float32)
    scale = np.abs(w).max() / 240.0
    want = (w / scale).astype(ml_dtypes.float8_e4m3).astype(np.float32) * scale
    assert np.mean(np.isclose(got, want, rtol=1e-6, atol=0)) > 0.999
    rel = np.abs(got - w) / np.abs(w)
    assert 0.01 < np.median(rel) < 0.0625 and len(np.unique(got)) < 256
    for path in (("final_norm", "scale"), ("exit_gate", "bias")):
        a, b = params, low
        for key in path:
            a, b = a[key], b[key]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
