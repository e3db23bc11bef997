"""Command A+ (``commanda``: CohereLabs command-a-plus-05-2026) on the normal
serving path, at tiny widths on the CPU: three window layers (RoPE, 32 keys)
to one full layer (no position signal) around a parallel block of 16
sigmoid-routed experts with four averaged shared experts, served through
``ContinuousBatchingEngine`` on a KV pool with TWO page classes, a share of
the experts held.  Everything is compared with the plain reference
(``benchmark/reference/commanda_block.py``) on the same weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import commanda_block
from benchmark.reference import common as ref_common
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.pools import (
    NULL_PAGE,
    PagedKVPool,
    PrefixCache,
    refuse_unserved,
)
from megatron_llm_tpu.models import init_model_params, make_config, moe
from megatron_llm_tpu.models.language_model import model_forward
from megatron_llm_tpu.models.transformer import pool_classes
from tests.parity import assert_memory, assert_memory_idle, held_pages

ATOL = 3e-5
VOCAB = 256
NEVER = 10 ** 9
WINDOW, PAGE = 32, 8

WIDTHS = dict(
    num_layers=4, hidden_size=64, num_attention_heads=8,
    num_attention_heads_kv=2, kv_channels=16, num_experts=16,
    moe_router_topk=4, moe_ffn_hidden_size=32, ffn_hidden_size=32,
    sliding_window_size=WINDOW, vocab_size=VOCAB, params_dtype="float32",
    use_flash_attn=False, max_position_embeddings=512, seq_length=256,
    # a share's row buffer takes every assignment: nothing is dropped
    moe_capacity_factor=8.0,
    # scores far enough apart that float32 rounding picks no other expert
    init_method_std=0.3)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
             head_dim=16, layer_norm_eps=1e-5, sliding_window=WINDOW,
             layer_types=["sliding_attention"] * 3 + ["full_attention"],
             rope_theta=50000, num_experts_per_tok=4, num_experts=16,
             num_shared_experts=4,
             shared_expert_combination_strategy="average", logit_scale=1)
HELD, FIRST = 4, 8


def commanda_cfg(**kw):
    return make_config("commanda", **{**WIDTHS, **kw})


@pytest.fixture(scope="module")
def whole():
    cfg = commanda_cfg()
    return cfg, init_model_params(cfg, jax.random.PRNGKey(0))


def share_of(params, first, held):
    """The tree of the chip that holds experts ``first .. first + held``."""
    layers = dict(params["layers"])
    m = dict(layers["moe"])
    m["experts"] = jax.tree.map(lambda a: a[:, first:first + held],
                                m["experts"])
    layers["moe"] = m
    return {**params, "layers": layers}


@pytest.fixture(scope="module")
def share(whole):
    _, params = whole
    cfg = commanda_cfg(moe_experts_held=HELD, moe_first_held_expert=FIRST)
    return cfg, share_of(params, FIRST, HELD)


def reference_log_probs(params, tokens, model=MODEL):
    tokens = jnp.asarray([tokens], jnp.int32)
    logits = commanda_block.logits(params, tokens, model)
    return np.asarray(ref_common.token_log_probs(logits, tokens))[0]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, VOCAB, n)] for n in lengths]


def engine(cfg, params, **kw):
    return ContinuousBatchingEngine(
        cfg, params, **{**dict(max_slots=4, page_size=PAGE, max_seq=256,
                               prefill_chunk=16), **kw})


# ---- the family ------------------------------------------------------------

def test_family_and_parameter_tree(whole):
    cfg, params = whole
    m = cfg.model
    assert m.parallel_attn and not m.use_rms_norm and not m.norm_bias
    assert m.sliding_window_layout == (1, 1, 1, 0) == m.rope_layout
    assert m.moe_shared_combination == "average" and m.tie_embed_logits
    layer = params["layers"]
    assert set(layer) == {"attention", "input_norm", "moe"}   # ONE norm
    assert set(layer["input_norm"]) == {"scale"} == set(params["final_norm"])
    assert "lm_head" not in params and "bias" not in layer["moe"]["router"]
    assert layer["moe"]["shared"]["fc1"]["kernel"].shape == (4, 64, 2, 4 * 32)
    full, window = pool_classes(cfg)
    assert (full.window, full.places) == (None, (3,))
    assert (window.window, window.places) == (WINDOW, (0, 1, 2))
    with pytest.raises(ValueError, match="averages its shared experts"):
        commanda_cfg(moe_shared_combination="sum")
    big = make_config("commanda-plus", vocab_size=262144)
    assert (big.model.num_layers, big.model.num_experts,
            big.model.num_attention_heads, big.model.rope_theta) == (
        32, 128, 128, 50_000.0)


def test_held_experts_go_with_shared_experts():
    """What config/arguments.py refused ("every chip would add the shared
    expert's output again"): with attention data-parallel each chip computes
    the shared experts for ITS OWN tokens, once."""
    cfg = commanda_cfg(moe_experts_held=HELD, moe_first_held_expert=FIRST)
    assert cfg.model.experts_held == HELD and cfg.model.moe_shared_experts == 4
    with pytest.raises(AssertionError, match="lie outside the router"):
        commanda_cfg(moe_experts_held=8, moe_first_held_expert=12)


@pytest.mark.parametrize("which", ["whole", "share"])
def test_dense_forward_matches_reference(whole, share, which):
    cfg, params = whole if which == "whole" else share
    model = {**MODEL, "first_held_expert": 0 if which == "whole" else FIRST}
    tokens = jnp.asarray(prompts(100, 100, seed=3), jnp.int32)   # 3+ windows
    got = model_forward(cfg, params, tokens)[0]
    want = commanda_block.logits(params, tokens, model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-4)


def test_shares_sum_to_the_uncut_layer(whole):
    """The guide's share test: the four shares' routed parts plus the
    averaged shared experts counted ONCE equal the uncut reference layer."""
    cfg, params = whole
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ht = x.reshape(48, 64)
        scores = jax.nn.sigmoid(ht @ layer["moe"]["router"]["kernel"])
        w = commanda_block.router_weights(scores, 4)
        uncut = (commanda_block.routed(
            params["layers"]["moe"]["experts"], 1, ht, w)
                 + 0.25 * commanda_block.shared(layer["moe"]["shared"], ht, 4))
        averaged = 0.25 * commanda_block.shared(layer["moe"]["shared"], ht, 4)
    total = 0.0
    for first in range(0, 16, HELD):
        scfg = commanda_cfg(moe_experts_held=HELD, moe_first_held_expert=first)
        p = {**layer["moe"], "experts": jax.tree.map(
            lambda a: a[first:first + HELD], layer["moe"]["experts"])}
        out, aux = moe.moe_sublayer(scfg, p, x)
        assert float(aux[5]) == 0 and float(aux[4]) <= float(aux[2])
        assert float(aux[6]) <= HELD
        total = total + (out.reshape(48, 64) - averaged)   # its routed part
    np.testing.assert_allclose(np.asarray(total + averaged),
                               np.asarray(uncut), rtol=0, atol=1e-4)


# ---- through the engine ------------------------------------------------------

def test_engine_matches_reference_on_two_page_classes(share):
    """Chunked prefill, then decode, through the ragged tick: sequences of
    three windows and more, two of them sharing ticks; then the benchmark's
    probes: a whole page-aligned prefix hit (copy-on-write in BOTH classes)
    and a partial hit with a suffix of its own, both ending far past the
    window."""
    cfg, params = share
    model = {**MODEL, "first_held_expert": FIRST}
    eng = engine(cfg, params)
    assert eng.pool.kv.shape[0] == 1 and eng.wpool.kv.shape[0] == 3
    assert eng.pool.page_class == "full" and eng.wpool.page_class == "window"
    a, b, c = prompts(100, 133, 60)
    reqs = [eng.submit(p, 24, top_k=1, termination_id=NEVER) for p in (a, b)]
    eng.run_until_idle()
    cached = (len(b) - 1) // PAGE * PAGE                     # 128 tokens
    later = [b[:cached], b[:cached - PAGE // 2] + c]
    for p in later:
        reqs.append(eng.submit(p, 24, top_k=1, termination_id=NEVER))
        eng.run_until_idle()
    assert eng.cow_copies == 1
    assert eng.prefix_hit_tokens == cached + cached - PAGE
    for req in reqs:
        tokens, lps = req.result(timeout=120)
        want = reference_log_probs(params, tokens, model)[len(req.prompt) - 1:]
        np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=ATOL)
    assert eng.window_pages_released > 0
    # the router's counts rode the fetches: held assignments are a share
    assert 0 < eng.moe_held_assignments < eng.moe_assignments
    assert 0 < eng.moe_held_experts_touched <= eng.ticks * 4 * HELD
    _assert_idle(eng)


PLANTED = {
    "window_ignored": dict(sliding_window_layout=(0, 0, 0, 0)),
    "rope_on_the_full_layer": dict(rope_layout=(1, 1, 1, 1)),
    "average_dropped": dict(moe_shared_combination="sum"),
    "an_absent_expert_given_a_row": dict(moe_first_held_expert=FIRST - HELD),
}


@pytest.mark.parametrize("fault", list(PLANTED))
def test_planted_faults_fail_the_reference(share, fault):
    """Each fault planted in the PROGRAM reads far outside what the honest
    program reads (3e-5): the comparison can see it."""
    _, params = share
    from megatron_llm_tpu.config.arguments import (
        Config,
        _set_flag,
        apply_architecture,
    )

    cfg = Config()
    apply_architecture(cfg, "commanda")
    for k, v in {**WIDTHS, "moe_experts_held": HELD,
                 "moe_first_held_expert": FIRST, **PLANTED[fault]}.items():
        _set_flag(cfg, k, v)
    cfg.finalize()                   # not validate_family: the fault is one
    (prompt,) = prompts(120, seed=9)
    model = {**MODEL, "first_held_expert": FIRST}
    if fault == "window_ignored":
        got = model_forward(cfg, params, jnp.asarray([prompt]))[0]
        got = np.asarray(ref_common.token_log_probs(
            got, jnp.asarray([prompt])))[0][-24:]
        want = reference_log_probs(params, prompt, model)[-24:]
    else:
        eng = engine(cfg, params)
        req = eng.submit(prompt, 24, top_k=1, termination_id=NEVER)
        eng.run_until_idle()
        tokens, got = req.result(timeout=120)
        want = reference_log_probs(params, tokens, model)[len(prompt) - 1:]
    assert np.abs(np.asarray(got) - want).mean() > 100 * ATOL


# ---- the pool's invariants, a class -------------------------------------------

def _assert_classes(eng):
    assert_memory(eng)            # every class: tests/parity.py
    full, win = eng._classes
    assert (full.pool, win.pool) == (eng.pool, eng.wpool)
    assert win.window == WINDOW and win.cap == eng.window_pages_cap
    if eng.cache is not None:
        assert set(eng.cache._nodes) == eng.pool.cached
        assert set(eng.cache._wnodes) == eng.wpool.cached
        for wp, node in eng.cache._wnodes.items():
            assert node.wpage == wp and eng.cache._nodes[node.page] is node
            # a window page referenced: so is its block's full page
            assert (eng.wpool.refcounts[wp] == 0
                    or eng.pool.refcounts[node.page] > 0)
    for r in eng._slots:
        if r is None:
            continue
        live = [p for p in r._mem[1].pages if p != NULL_PAGE]
        assert len(live) <= eng.window_pages_cap
        if r._phase == "decode":
            assert len(live) <= -(-WINDOW // PAGE) + 2


_assert_idle = assert_memory_idle


@pytest.mark.parametrize("seed", [0, 1])
def test_pool_invariants_hold_step_by_step(share, seed):
    """A small engine under shared prefixes, preemption and a window pool
    that runs dry: after every step the page states of EACH class are
    disjoint and counted, no sequence holds more window pages than its
    cap, and the window class's ledger equals what its requests may still
    take.  At the end everything is back, in both classes."""
    _, params = share
    eng = ContinuousBatchingEngine(
        make_config("commanda", **{**WIDTHS, "moe_experts_held": HELD,
                                   "moe_first_held_expert": FIRST,
                                   "kv_window_pool_pages": 41}),
        params, max_slots=4, page_size=PAGE, max_seq=200, prefill_chunk=16,
        num_pages=120)
    assert eng.wpool.num_pages == 41
    rng = np.random.default_rng(seed)
    base = prompts(96, 96, seed=seed + 10)
    reqs = []
    for i in range(10):
        stem = base[i % 2][: int(rng.integers(5, 12)) * PAGE]
        tail = [int(t) for t in rng.integers(1, VOCAB, int(rng.integers(0, 20)))]
        reqs.append(eng.submit(stem + tail, int(rng.integers(4, 30)),
                               top_k=1, termination_id=NEVER))
    steps = 0
    while True:
        n = eng.step()
        steps += 1
        _assert_classes(eng)
        if steps in (9, 17):               # preemption gives back both classes
            victim = next((r for r in eng._slots
                           if r is not None and r._phase == "decode"), None)
            if victim is not None and eng.preempt(victim):
                assert not held_pages(victim)
                _assert_classes(eng)
        if n == 0 and not eng._queue and all(r is None for r in eng._slots):
            break
        assert steps < 2000
    for r in reqs:
        assert r.result(timeout=60) and not r.error
    assert eng.window_pages_released > 0 and eng.preemptions >= 1
    _assert_idle(eng)


def test_preempted_and_resumed_matches_reference(share):
    cfg, params = share
    model = {**MODEL, "first_held_expert": FIRST}
    eng = engine(cfg, params)
    (p,) = prompts(90, seed=4)
    req = eng.submit(p, 30, top_k=1, termination_id=NEVER)
    while len(req.generated) < 12:
        eng.step()
    assert eng.preempt(req) and not held_pages(req)
    eng.run_until_idle()
    tokens, lps = req.result(timeout=60)
    assert req._preemptions == 1 and eng.prefix_hit_tokens > 0
    want = reference_log_probs(params, tokens, model)[len(p) - 1:]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=ATOL)
    _assert_idle(eng)


def test_a_match_is_shortened_when_the_window_class_lost_a_page(share):
    """Pool and trie alone: a chain of 12 blocks registered in both classes;
    the window class evicts its least recently used page (block 4, the
    shallowest it holds) and the match that needed it ends before it needs
    it; a block past the window's reach costs the match nothing."""
    cfg, _ = share
    ps = 2
    pool = PagedKVPool(cfg, 40, ps, layers=1, page_class="full")
    wpool = PagedKVPool(cfg, 40, ps, layers=3, page_class="window")
    cache = PrefixCache(pool, ps, wpool, window=8)       # 4 blocks of window
    tokens = list(range(1, 25))
    pages, wpages = pool.alloc(12), wpool.alloc(12)
    held = [NULL_PAGE] * 4 + wpages[4:]                  # the window slid
    wpool.release(wpages[:4])
    assert cache.insert(tokens, pages, 12, held) == 12
    pool.release(pages)
    wpool.release(held[4:])
    assert wpool.num_evictable == 8 and wpool.num_free == 39 - 8
    assert cache.window_first(12) == 8 and cache.window_first(3) == 0

    got, wgot = cache.match_classes(tokens, 12)          # needs blocks 8..11
    assert got == pages and wgot == [NULL_PAGE] * 8 + wpages[8:]
    pool.release(got)
    wpool.release(wgot[8:])
    got, wgot = cache.match_classes(tokens, 7)           # needs blocks 3..6
    assert len(got) == 0, "block 3's window page never was registered"

    assert cache.evict_window(1) == [wpages[4]]          # oldest stamp, first in
    assert cache._nodes[pages[4]].wpage == NULL_PAGE and len(cache) == 12
    got, wgot = cache.match_classes(tokens, 12)          # block 4 not needed
    assert len(got) == 12
    pool.release(got)
    wpool.release(wgot[8:])
    got, wgot = cache.match_classes(tokens, 8)           # needs 4..7: 4 is gone
    assert got == [] and wgot == []
    # evicting a node frees its page in both classes
    freed = cache.evict(2)
    assert freed == [pages[11], pages[10]]
    assert wpages[11] in wpool._free and wpages[10] in wpool._free
    assert set(cache._wnodes) == wpool.cached == set(wpages[5:10])


# ---- a uniform model is one class, as it was -----------------------------------

def test_a_uniform_model_keeps_one_class():
    cfg = make_config("mistral", num_layers=2, hidden_size=64,
                      num_attention_heads=4, num_attention_heads_kv=2,
                      ffn_hidden_size=128, vocab_size=VOCAB,
                      sliding_window_size=32, params_dtype="float32",
                      use_flash_attn=False, max_position_embeddings=256)
    (only,) = pool_classes(cfg)
    assert only.window is None and only.places == (0,)   # its pool never slides
    eng = ContinuousBatchingEngine(
        cfg, init_model_params(cfg, jax.random.PRNGKey(0)), max_slots=2,
        page_size=PAGE, max_seq=128)
    assert eng.wpool is None and len(eng._classes) == 1
    assert eng._class_statics == () and eng.pool.page_class is None
    assert eng._kv is eng.pool.kv and eng.pool.kv.shape[0] == 2
    req = eng.submit(prompts(70)[0], 8, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    assert req.result(timeout=60) and eng.window_pages_released == 0
    assert not held_pages(req) and eng._classes[0].committed == 0


# ---- what two classes do not carry yet says so ----------------------------------

def _mesh(**axes):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(**axes, data_parallel_size=1, devices=jax.devices()[:n])


@pytest.mark.parametrize("kw, sentence", [
    (dict(kv_dtype="int8"), "--kv_dtype int8"),
    (dict(mesh="tp"), "tensor- or pipeline-parallel serving"),
    (dict(draft=True), "--spec_k"),
    (dict(handoff=True), "cross-replica KV handoff"),
])
def test_page_class_refusals(whole, kw, sentence):
    cfg, _ = whole
    if kw.get("mesh"):
        kw = dict(mesh=_mesh(tensor_model_parallel_size=2))
    with pytest.raises(ValueError, match="two\\s+page classes") as e:
        refuse_unserved(cfg, **kw)
    assert sentence in str(e.value)
    refuse_unserved(cfg)                 # one chip, bf16: served


def test_refusals_at_the_engines_door(whole, share):
    cfg, params = whole
    with pytest.raises(ValueError, match="--kv_dtype int8"):
        engine(cfg, params, kv_dtype="int8")
    scfg, sparams = share
    with pytest.raises(ValueError, match="one chip's share"):
        refuse_unserved(scfg, mesh=_mesh(tensor_model_parallel_size=2))
    eng = engine(scfg, sparams)
    with pytest.raises(gen.InvalidRequest, match="return_log_probs"):
        eng.submit([1, 2, 3], 4, return_log_probs=True)
    with pytest.raises(ValueError, match="cross-replica KV handoff"):
        eng.prefill_and_export([1, 2, 3])
    from megatron_llm_tpu.generation.server import MegatronServer

    with pytest.raises(ValueError, match="cross-replica KV handoff"):
        MegatronServer(eng, role="prefill")
