"""JetLM SDAR (``sdar_moe``: SDAR-30B-A3B-Chat) on the normal serving path, at
tiny widths on the CPU: QK-normed rotated GQA under a BLOCK-causal mask,
softmax-routed SwiGLU experts over a held share, and generation by diffusion
over blocks of 4 through the engine's block tick (generation/blocks.py).
Everything is compared with the plain reference
(``benchmark/reference/sdar_block.py``) on the same weights: the dense
forward with its ``logits``, the engine with its ``generate`` (tokens AND the
log-probability of the step that unmasked each), its two-stream ``stack``
with its own ``generate``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import sdar_block as ref
from megatron_llm_tpu.config.arguments import MODEL_SIZES, parse_args
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import blocks as blocks_mod
from megatron_llm_tpu.generation import engine as engine_mod
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.pools import (
    KEEPS,
    NOT_CARRIED,
    memory_kind,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models import moe as moe_mod
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.observability import registry as obs_registry
from tests.parity import assert_memory, assert_memory_idle

ATOL = 1e-4
VOCAB, MASK = 96, 95
PAGE, B = 8, 4
WIDTHS = dict(
    num_layers=2, hidden_size=64, num_attention_heads=4,
    num_attention_heads_kv=2, kv_channels=16, ffn_hidden_size=32,
    num_experts=8, moe_router_topk=2, moe_ffn_hidden_size=32,
    vocab_size=VOCAB, mask_token_id=MASK, max_position_embeddings=256,
    seq_length=256, params_dtype="float32", use_flash_attn=False)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(
    num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, rope_theta=1000000,
    vocab_size=VOCAB, diffusion_block_length=B, mask_token_id=MASK)
STRATEGIES = blocks_mod.STRATEGIES


def sdar_cfg(**kw):
    return make_config("sdar_moe", **{**WIDTHS, **kw})


def drawn(params, seed=3, sharp=8.0, favour=None):
    """The initialiser leaves every norm's scale at 1 and, at std 0.02, every
    distribution over the vocabulary flat: draw the norms, and make the head
    SHARP, so that a norm the program forgot would show, confidences spread
    from ~0.1 to ~0.9 and some pass a threshold of 0.5.  ``favour``: a token
    id whose column of the head is raised, so that greedy rows emit it."""
    key = jax.random.PRNGKey(seed)
    out = jax.tree.map(lambda a: a, params)
    att = out["layers"]["attention"]
    for i, node in enumerate((out["layers"]["input_norm"],
                              out["layers"]["post_norm"], out["final_norm"],
                              att["q_norm"], att["k_norm"])):
        node["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), node["scale"].shape)
    head = out["lm_head"]["kernel"] * sharp
    if favour is not None:
        head = head.at[:, favour].set(jnp.abs(head[:, favour]) * 0 + 0.35)
    out["lm_head"]["kernel"] = head
    out["embedding"]["word_embeddings"] = (
        out["embedding"]["word_embeddings"] * 20.0)
    return out


@pytest.fixture(scope="module")
def model():
    cfg = sdar_cfg()
    return cfg, drawn(init_model_params(cfg, jax.random.PRNGKey(0)))


def engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, **{**dict(max_slots=4, page_size=PAGE, max_seq=256,
                               prefill_chunk=16), **kw})


@pytest.fixture(scope="module")
def served(model):
    return engine(*model)


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, MASK, n)] for n in lengths]


def ask(eng, prompt, n, strategy="sequential", steps=4, threshold=0.5, **kw):
    return eng.submit(prompt, n, top_k=1, use_eod_for_termination=False,
                      remasking_strategy=strategy, denoising_steps=steps,
                      confidence_threshold=threshold, **kw)


def check(req, params, strategy="sequential", steps=4, threshold=0.5,
          model=MODEL):
    tokens, lps = req.result(timeout=120)
    n = len(tokens) - len(req.prompt)
    want, _, want_lp = ref.generate(params, req.prompt, n, model, strategy,
                                    steps, threshold)
    assert tokens[len(req.prompt):] == want
    np.testing.assert_allclose(lps, want_lp, rtol=0, atol=ATOL)
    return want


def _assert_idle(eng):
    assert all(r is None for r in eng._slots) and not eng._inflight
    assert_memory_idle(eng)


# ---- the family ------------------------------------------------------------

def test_family_preset_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert cfg.model_name == "sdar_moe" and memory_kind(cfg) == "blocks"
    assert m.diffusion_block_length == B and m.mask_token_id == MASK
    assert m.qk_head_norm and m.moe_score_func == "softmax"
    assert m.moe_normalize_gates and not m.tie_embed_logits
    assert params["layers"]["moe"]["router"]["kernel"].shape == (2, 64, 8)
    assert params["layers"]["attention"]["q_norm"]["scale"].shape == (2, 16)
    size = MODEL_SIZES["sdar-30b-a3b-chat"]
    assert (size["num_layers"], size["hidden_size"], size["num_experts"],
            size["moe_router_topk"], size["moe_ffn_hidden_size"],
            size["vocab_size"], size["diffusion_block_length"],
            size["mask_token_id"]) == (48, 2048, 128, 8, 768, 151936, 4,
                                       151669)
    big = parse_args(["--model_name", "sdar-30b-a3b-chat",
                      "--tokenizer_type", "NullTokenizer"])
    assert big.model_name == "sdar_moe" and big.model.kv_channels == 128
    assert big.model.num_attention_heads_kv == 4
    shapes = jax.eval_shape(lambda k: init_model_params(big, k),
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # 48 x 623.1 M + an embedding and an untied head of 151,936 rows
    layer = (2048 * 5120 + 4096 * 2048 + 2 * 128 + 2 * 2048 + 2048 * 128
             + 128 * 3 * 2048 * 768)
    assert layer == 623_120_640
    assert total == 48 * layer + 2 * 151_936 * 2048 + 2048


@pytest.mark.parametrize("flag, value, said", [
    ("mask_token_id", None, "mask_token_id"),
    ("qk_head_norm", False, "norms q and k"),
    ("moe_score_func", "sigmoid", "softmax over all experts"),
    ("moe_normalize_gates", False, "normalised"),
    ("tie_embed_logits", True, "untied head"),
    ("moe_shared_experts", 1, "no shared expert"),
])
def test_family_refuses_another_block(flag, value, said):
    with pytest.raises((ValueError, AssertionError), match=said):
        sdar_cfg(**{flag: value})


def test_block_mask_needs_one_class_of_kv_pages():
    with pytest.raises(AssertionError, match="one class of K/V"):
        sdar_cfg(sliding_window_size=32)


# ---- the dense forward -----------------------------------------------------

@pytest.mark.parametrize("length", [7, 16, 41])
def test_dense_forward_matches_reference_logits(model, length):
    cfg, params = model
    tokens = jnp.asarray(prompts(length, seed=length), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, MODEL)
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)


def test_dense_mask_is_block_causal_not_causal(model):
    """A token reaches every position of ITS block and of later ones, and no
    earlier block."""
    cfg, params = model
    tokens = jnp.asarray(prompts(14, seed=1), jnp.int32)
    base, _ = model_forward(cfg, params, tokens)
    moved, _ = model_forward(cfg, params, tokens.at[0, 6].set(3))
    delta = np.asarray(jnp.abs(base - moved).max(axis=-1))[0]
    assert (delta[:4] == 0).all() and (delta[4:] > 0).all()


@pytest.mark.parametrize("choice, turned", [
    ("mask_end", lambda pos, model: pos + 1),
    ("qk_normed", lambda model: False),
    ("rope_position", lambda pos, model: ref.mask_end(pos, model) - 1),
    ("gates_normalised", lambda model: False),
])
def test_dense_forward_sees_a_turned_reading(model, choice, turned,
                                             monkeypatch):
    """Each reading the reference holds in one function is one the program's
    numbers depend on."""
    cfg, params = model
    tokens = jnp.asarray(prompts(22, seed=2), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    monkeypatch.setattr(ref, choice, turned)
    other = ref.logits(params, tokens, MODEL)
    assert float(jnp.abs(jax.nn.log_softmax(logits)
                         - jax.nn.log_softmax(other)).max()) > 50 * ATOL


def test_dense_forward_of_a_held_share_matches_reference():
    cfg = sdar_cfg(moe_experts_held=2, moe_first_held_expert=4,
                   moe_capacity_factor=4.0)
    params = drawn(init_model_params(cfg, jax.random.PRNGKey(1)))
    assert params["layers"]["moe"]["experts"]["fc2"]["kernel"].shape == (
        2, 2, 32, 64)
    tokens = jnp.asarray(prompts(30, seed=2), jnp.int32)
    logits, _ = model_forward(cfg, params, tokens)
    want = ref.logits(params, tokens, {**MODEL, "first_held_expert": 4})
    np.testing.assert_allclose(jax.nn.log_softmax(logits),
                               jax.nn.log_softmax(want), rtol=0, atol=ATOL)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """ONE expert layer of 16 experts, each of 8 chips holding 2 of them and
    the router's 16 outputs and top-4: the chips' parts add up to what the
    uncut reference gives for the whole layer."""
    kw = dict(num_layers=1, num_experts=16, moe_router_topk=4)
    whole_cfg = sdar_cfg(**kw)
    p = jax.tree.map(lambda a: a[0], init_model_params(
        whole_cfg, jax.random.PRNGKey(4))["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 37, 64))
    model = {**MODEL, "num_experts_per_tok": 4}
    want = ref.experts(p, x, model)
    program, _ = moe_mod.moe_sublayer(whole_cfg, p, x)
    np.testing.assert_allclose(program, want, rtol=0, atol=2e-5)
    total = 0.0
    for chip in range(8):
        cfg = sdar_cfg(**kw, moe_experts_held=2,
                       moe_first_held_expert=2 * chip,
                       moe_capacity_factor=8.0)
        held = {**p, "experts": jax.tree.map(
            lambda a: a[2 * chip:2 * chip + 2], p["experts"])}
        part, aux = moe_mod.moe_sublayer(cfg, held, x)
        assert float(aux[5]) == 0              # no held assignment dropped
        ref_part = ref.experts(held, x, {**model,
                                         "first_held_expert": 2 * chip})
        np.testing.assert_allclose(part, ref_part, rtol=0, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-5)


# ---- the reference against itself ------------------------------------------

@pytest.mark.parametrize("remainder", range(B))
def test_two_stream_stack_equals_generate_under_sequential(model, remainder):
    """``stack`` as ``benchmark/lib/check.py`` calls it (the probe's prompt +
    tokens right-padded, rows ``len(prompt) - 1 + i``) gives the
    log-probability the published loop gave each token at the step that
    unmasked it."""
    _, params = model
    prompt, = prompts(12 + remainder, seed=10 + remainder)
    n = 9
    want, steps, want_lp = ref.generate(params, prompt, n, MODEL,
                                        "sequential", 4)
    assert steps == sorted(steps) and len(set(steps)) == n
    row = np.ones((1, len(prompt) + n), np.int32)    # ends mid-block
    row[0, :len(prompt) + n] = prompt + want
    hidden = ref.stack(params, jnp.asarray(row), MODEL)[0]
    start = len(prompt) - 1
    lp = ref_common.emitted_log_probs(
        ref.head(params, hidden[start:start + n], MODEL),
        jnp.asarray(want, jnp.int32))
    np.testing.assert_allclose(lp, want_lp, rtol=0, atol=ATOL)


def test_chosen_positions_by_strategy():
    masked = np.array([True, False, True, True])
    conf = np.array([0.2, 0.99, 0.7, 0.6])
    pick = lambda *a: ref.chosen(masked, conf, *a).tolist()  # noqa: E731
    assert pick("sequential", 1, 0.9) == [True, False, False, False]
    assert pick("sequential", 2, 0.9) == [True, False, True, False]
    assert pick("low_confidence_static", 1, 0.9) == [False, False, True, False]
    assert pick("low_confidence_dynamic", 1, 0.9) == [
        False, False, True, False]
    assert pick("low_confidence_dynamic", 1, 0.5) == [
        False, False, True, True]
    got = blocks_mod.unmask(
        jnp.asarray(~masked)[None], jnp.ones((1,), bool),
        jnp.asarray(conf)[None], jnp.asarray([2]), jnp.asarray([1]),
        jnp.asarray([0.5], jnp.float32))
    assert np.asarray(got)[0].tolist() == [False, False, True, True]


# ---- the engine ------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("steps", [4, 2])
@pytest.mark.parametrize("remainder", range(B))
@pytest.mark.parametrize("ends_at", range(B))
def test_engine_generates_as_the_published_loop(model, served, strategy,
                                                steps, remainder, ends_at):
    """Tokens equal and log-probabilities to 1e-4, for every strategy, 4 and
    2 steps a block, a prompt that ends at every offset of a block (the
    first generated block opens on its remainder) and an output that ends
    at every offset of one (``max_new_tokens`` cuts inside a block)."""
    _, params = model
    plen = 16 + remainder
    n = 8 + (ends_at - plen) % B
    prompt, = prompts(plen, seed=100 * remainder + ends_at)
    req = ask(served, prompt, n, strategy, steps)
    served.run_until_idle()
    assert (plen + n) % B == ends_at
    check(req, params, strategy, steps)
    _assert_idle(served)


def test_a_step_unmasks_several_tokens_where_confidence_passes(model, served):
    _, params = model
    prompt, = prompts(19, seed=5)
    _, _, lps = ref.generate(params, prompt, 16, MODEL, "sequential", 4)
    threshold = float(np.exp(np.median(lps)))      # half the samples pass it
    _, steps, _ = ref.generate(params, prompt, 16, MODEL,
                               "low_confidence_dynamic", 4, threshold)
    assert len(set(steps)) < 16          # some step unmasked more than one
    req = ask(served, prompt, 16, "low_confidence_dynamic", 4, threshold)
    served.run_until_idle()
    check(req, params, "low_confidence_dynamic", 4, threshold)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_alone_and_among_others_bitwise(model, strategy):
    """A request's tokens AND log-probabilities do not depend on what else
    the ticks carry: prompt rows of others, their denoise and commit rows,
    their strategies.  Compared in the same slot: a row's place in the batch
    is its slot's, and XLA:CPU's matmul rounds a row by its place (the
    remainder panel of its blocking)."""
    cfg, params = model
    mine, *others = prompts(21, 9, 34, 18, 50, seed=7)
    solo = engine(cfg, params)
    alone = ask(solo, mine, 11, strategy, 2)
    solo.run_until_idle()
    eng = engine(cfg, params)
    among = ask(eng, mine, 11, strategy, 2)
    for i, p in enumerate(others):
        ask(eng, p, 9 + i, STRATEGIES[i % 3], 4 if i % 2 else 2)
    eng.run_until_idle()
    assert alone.result() == among.result()
    _assert_idle(eng)


@pytest.mark.parametrize("kind", ["whole", "part"])
def test_prefix_hit_equals_the_cold_run(model, kind):
    """A whole-page match that ends on the prompt (no page is copied: a
    block model writes no shared page) and a partial one (the trie holds
    whole pages, ``page_size % B == 0``, so a match ends on a block
    boundary) read the K/V a cold prefill computes."""
    cfg, params = model
    first, fresh = prompts(43, 43, seed=11)
    cached = (len(first) - 1) // PAGE * PAGE
    prompt = (first[:cached] if kind == "whole"
              else first[:cached - PAGE // 2] + fresh[cached - PAGE // 2:])
    cold = engine(cfg, params, prefix_cache=False)
    want = ask(cold, prompt, 10)
    cold.run_until_idle()
    eng = engine(cfg, params)
    ask(eng, first, 6)
    eng.run_until_idle()
    before = eng.prefix_hit_tokens
    got = ask(eng, prompt, 10)
    eng.run_until_idle()
    hit = eng.prefix_hit_tokens - before
    assert hit == (cached if kind == "whole" else cached - PAGE)
    assert hit % B == 0 and eng.cow_copies == 0
    assert got.result() == want.result()
    check(got, params)
    _assert_idle(eng)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_preempted_and_resumed_equals_uninterrupted(model, strategy):
    """A preempted request keeps its committed blocks (the trie's) and draws
    the block in flight again: the same tokens, bit for bit, none streamed
    twice."""
    cfg, params = model
    obs_registry.set_publishing(True)
    prompt, = prompts(22, seed=13)
    plain = engine(cfg, params)
    want = ask(plain, prompt, 21, strategy)
    plain.run_until_idle()
    eng = engine(cfg, params)
    req, q = eng.submit_stream(
        prompt, 21, top_k=1, use_eod_for_termination=False,
        remasking_strategy=strategy, denoising_steps=4,
        confidence_threshold=0.5)
    while len(req.generated) < 10:
        eng.step()
        assert_memory(eng)
    before = obs_registry.get_registry().counter(
        "mlt_engine_block_recomputed_total").value
    assert eng.preempt(req)
    assert_memory(eng)
    eng.run_until_idle()
    assert req.result() == want.result()
    assert req._preemptions == 1 and req._hit_tokens >= 24
    assert obs_registry.get_registry().counter(
        "mlt_engine_block_recomputed_total").value == before + 1
    streamed = [t for ev in q.take_tokens() for t in ev.tokens]
    assert streamed == req.generated
    _assert_idle(eng)


def test_mask_id_in_a_prompt_and_as_an_emitted_token():
    """Whether a position is masked is its KNOWN flag, never ``id ==
    mask_token_id``: a prompt may hold that id, and greedy rows may emit
    it."""
    cfg = sdar_cfg()
    params = drawn(init_model_params(cfg, jax.random.PRNGKey(0)),
                   favour=MASK)
    prompt, = prompts(18, seed=3)
    prompt[5] = prompt[17] = MASK          # the second in the open block
    eng = engine(cfg, params)
    req = ask(eng, prompt, 12)
    eng.run_until_idle()
    want = check(req, params)
    assert MASK in want


def test_a_stop_token_inside_a_block_cuts_what_follows(model, served):
    _, params = model
    prompt, = prompts(17, seed=21)
    want, _, _ = ref.generate(params, prompt, 12, MODEL, "sequential", 4)
    stop = want[5]
    cut = want.index(stop) + 1
    req = served.submit(prompt, 12, top_k=1, termination_id=stop,
                        remasking_strategy="sequential", denoising_steps=4)
    served.run_until_idle()
    assert req.result()[0][len(prompt):] == want[:cut]
    _assert_idle(served)


def test_block_counters_count_rows_steps_and_blocks(model):
    cfg, params = model
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    names = ("denoise_rows", "commit_rows", "steps", "slot_ticks",
             "tokens_unmasked")
    read = lambda: {n: reg.counter(  # noqa: E731
        f"mlt_engine_block_{n}_total").value for n in names} | {
        "committed": reg.counter("mlt_engine_blocks_committed_total").value}
    before = read()
    eng = engine(cfg, params)
    prompt, = prompts(16, seed=2)
    req = ask(eng, prompt, 12)             # three whole blocks, 4 steps each
    eng.run_until_idle()
    assert len(req.generated) == 12
    got = {n: v - before[n] for n, v in read().items()}
    assert got == {"denoise_rows": 48, "commit_rows": 8, "steps": 12,
                   "slot_ticks": 12, "tokens_unmasked": 12, "committed": 2}


def test_block_ticks_count_walks_and_blocks_by_the_kernels_rule(monkeypatch):
    """A block tick's rows name ONE table, so the kernel walks a slot's
    span once through its last compute block, and the engine's counters
    follow the same rule: past one compute block of context (256 tokens
    on this pool) a denoise tick's four rows see two blocks each and fetch
    two in ONE walk, and so do a commit tick's eight."""
    cfg = sdar_cfg(max_position_embeddings=512, seq_length=512)
    params = drawn(init_model_params(cfg, jax.random.PRNGKey(0)))
    layers = cfg.model.num_layers
    obs_registry.set_publishing(True)
    reg = obs_registry.get_registry()
    read = lambda: np.array([reg.counter(  # noqa: E731
        f"mlt_engine_paged_{n}_total").value
        for n in ("rows", "walks", "blocks_seen", "blocks_fetched")])
    given, rule = [], engine_mod.tile_shares    # the engine counts both ticks

    def spy(table, idx, *rest, **kw):
        shares = rule(table, idx, *rest, **kw)
        seen, fetched = (int(n) for n in shares.blocks())
        given.append((int(idx.max()), int((idx > 0).sum()),
                      int(shares.walks()), seen, fetched))
        return shares

    monkeypatch.setattr(engine_mod, "tile_shares", spy)
    eng = engine(cfg, params, max_seq=512)
    before = read()
    prompt, = prompts(264, seed=4)
    req = ask(eng, prompt, 8)               # two whole blocks, 4 steps each
    eng.run_until_idle()
    assert len(req.generated) == 8
    counted = np.array([g[1:] for g in given]).sum(axis=0)
    assert (read() - before).tolist() == (
        counted * [1, 1, layers, layers]).tolist()
    # the ticks of block rows alone: seven of four denoise rows, and the
    # one that commits the first block beside the second's first step
    ticks = [g[1:] for g in given if 0 < g[0] <= eng.max_slots]
    assert sorted(ticks) == [(4, 1, 8, 2)] * 7 + [(8, 1, 16, 2)]


# ---- what it does not carry -------------------------------------------------

@pytest.mark.parametrize("feature", ["kv_dtype", "draft", "handoff",
                                     "log_probs"])
def test_refusals_in_a_sentence(model, feature):
    cfg, _ = model
    kw = {"kv_dtype": dict(kv_dtype="int8"), "draft": dict(draft=True),
          "handoff": dict(handoff=True),
          "log_probs": dict(log_probs=True)}[feature]
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, **kw)
    assert KEEPS["blocks"].split("{")[0] in str(e.value)
    assert NOT_CARRIED["blocks", feature].split("{")[0] in str(e.value)


def test_engine_and_requests_refuse_in_a_sentence(model, served):
    cfg, params = model
    with pytest.raises(ValueError, match="--kv_dtype int8"):
        engine(cfg, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="must divide page_size"):
        engine(cfg, params, page_size=2, prefill_chunk=2)
    with pytest.raises(gen.InvalidRequest, match="return_log_probs"):
        served.submit([1, 2, 3], 4, return_log_probs=True)
    with pytest.raises(gen.InvalidRequest, match="remasking_strategy"):
        served.submit([1, 2, 3], 4, remasking_strategy="random")
    with pytest.raises(gen.InvalidRequest, match="denoising_steps"):
        served.submit([1, 2, 3], 4, denoising_steps=3)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        served.beam_search_and_post_process(["x"], 4)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        served.generate_and_post_process(["x"], 0)


def test_a_causal_model_refuses_a_block_request_and_builds_no_driver():
    cfg = make_config("llama2", num_layers=1, hidden_size=64,
                      num_attention_heads=4, ffn_hidden_size=96,
                      vocab_size=VOCAB, max_position_embeddings=256,
                      seq_length=256, params_dtype="float32",
                      use_flash_attn=False)
    eng = engine(cfg, init_model_params(cfg, jax.random.PRNGKey(0)))
    assert eng._blocks is None
    with pytest.raises(gen.InvalidRequest, match="block model"):
        eng.submit([1, 2, 3], 4, denoising_steps=2)
