"""JoyAI-LLM-Flash (ISSUE 31): latent attention on a one-leaf paged cache,
a dense prefix before the expert layers, a bias-corrected sigmoid router with
a shared expert on the dropless dispatch — at tiny widths with the real
structure (1 dense + 2 expert layers, 16 experts top-4, one shared;
q_lora_rank / kv_lora_rank / nope / rope / v all distinct), seeded random
float32 weights, against ``benchmark/reference/joyai_block.py``.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums only (absorbed against expanded attention, a sorted
grouped GEMM against a masked loop over every expert, a paged cache against
none): logits and log-probabilities agree to ``ATOL`` = 2e-5, some ten ulps
of the values compared.  The router's choice is discrete, so every test
sets ``e_score_correction_bias`` to values (std 0.5) that decide the top-4
far beyond any rounding, and that a program which ignored them could not
survive: ``test_ignored_selection_bias_fails_the_reference`` shows the
failure (logits off by over 1e-2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import joyai_block
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation.pools import refuse_unserved
from megatron_llm_tpu.models import init_model_params, make_config, moe
from megatron_llm_tpu.models.language_model import (
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.models.transformer import LayerPool, mla_sublayer
from megatron_llm_tpu.ops.paged_attention import PagedState

import parity

ATOL = 2e-5
VOCAB = 256
NEVER = 10 ** 9          # a termination id no token reaches

WIDTHS = dict(
    num_layers=2, hidden_size=64, num_attention_heads=4, ffn_hidden_size=160,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, num_experts=16, moe_router_topk=4, moe_ffn_hidden_size=40,
    vocab_size=VOCAB, params_dtype="float32", use_flash_attn=False,
    max_position_embeddings=512)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=24,
             qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6,
             rope_theta=32_000_000, num_experts_per_tok=4,
             routed_scaling_factor=2.5)


def joyai_cfg(**kw):
    return make_config("joyai", **{**WIDTHS, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = joyai_cfg(max_batch_slots=4, engine_max_seq=256, page_size=16)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    bias = params["layers"]["moe"]["router"]["bias"]
    params["layers"]["moe"]["router"]["bias"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(7), bias.shape, bias.dtype)
    return cfg, params


def reference_log_probs(params, tokens):
    tokens = jnp.asarray([tokens], jnp.int32)
    logits = joyai_block.logits(params, tokens, MODEL)
    return np.asarray(ref_common.token_log_probs(logits, tokens))[0]


def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert (m.mla, m.dense_prefix_layers, m.depth) == (True, 1, 3)
    assert (m.moe_score_func, m.moe_selection_bias) == ("sigmoid", True)
    assert m.latent_cache_width == 40 and m.num_attention_heads_kv == 1
    assert "mlp" in params["dense_layers"] and "moe" not in params["dense_layers"]
    layer = params["layers"]
    assert layer["moe"]["experts"]["fc1"]["kernel"].shape == (2, 16, 2, 64, 40)
    assert layer["moe"]["shared"]["fc1"]["kernel"].shape == (2, 64, 2, 40)
    assert layer["moe"]["router"]["bias"].shape == (2, 16)
    assert layer["attention"]["kv_down"]["kernel"].shape == (2, 64, 40)
    assert layer["attention"]["kv_up"]["kernel"].shape == (2, 32, 4, 40)
    with pytest.raises(AssertionError, match="needs"):
        make_config("joyai", **{**WIDTHS, "kv_lora_rank": None})
    big = make_config("joyai-llm-flash", vocab_size=129280)
    assert (big.model.depth, big.model.num_experts,
            big.model.latent_cache_width) == (40, 256, 576)


def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, VOCAB)
    out, _ = model_forward(cfg, params, tokens)
    want = joyai_block.logits(params, tokens, MODEL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=ATOL)


def test_ignored_selection_bias_fails_the_reference(model):
    """What the tolerance is for: a program that drops the selection bias
    picks other experts and lands far outside it."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, VOCAB)
    broken = jax.tree.map(lambda a: a, params)
    broken["layers"]["moe"]["router"]["bias"] = jnp.zeros((2, 16))
    out, _ = model_forward(cfg, broken, tokens)
    want = joyai_block.logits(params, tokens, MODEL)
    assert float(jnp.abs(out - want).max()) > 500 * ATOL


def test_absorbed_equals_expanded(model):
    """One MLA sublayer: the expanded form on a whole sequence against the
    absorbed form fed the same tokens as single rows through a latent pool
    (layer 1 of a 3-layer pool, pages out of order)."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"]["attention"])
    s, page, pages = 40, 16, 9
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, 64), jnp.float32)
    rope = make_rope_cache(cfg)
    pos = jnp.arange(s)[None]
    want, none = mla_sublayer(cfg, p, x, rope, pos, None)
    assert none is None
    pool = jnp.full((3, pages, page, 128), jnp.nan, jnp.float32)
    pool = pool.at[:, :, :, :].set(7.0)     # stale values of another tenant
    table = jnp.asarray([[5, 2, 8, 0]], jnp.int32)
    rows = x[0][:, None, :]                                  # [s, 1, h]
    got, new_pool = mla_sublayer(
        cfg, p, rows, rope, jnp.arange(s)[:, None], None,
        kv_cache=LayerPool(pool, jnp.asarray(1)),
        paged=PagedState(table, jnp.arange(s, dtype=jnp.int32),
                         jnp.full((s,), 64, jnp.int32),
                         jnp.zeros((s,), jnp.int32)))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want[0]),
                               rtol=0, atol=ATOL)
    # one row a token in layer 1's pages 5, 2, 8; nothing anywhere else
    assert float(jnp.abs(new_pool[1, 5, :, :40]).max()) < 7.0
    assert bool((new_pool[1, 5, :, 40:] == 0).all())         # the lane pad
    untouched = new_pool.at[1, jnp.asarray([5, 2, 8])].set(7.0)
    assert bool((untouched == 7.0).all())


PROMPT_A = np.random.default_rng(0).integers(0, VOCAB, 70).tolist()
PROMPT_B = np.random.default_rng(1).integers(0, VOCAB, 100).tolist()


def test_engine_matches_reference_through_the_latent_pool(model):
    """Chunked prefill, then decode, through the ragged tick and the latent
    pool: the log-probability the engine reports for every token it emits
    against the reference's full forward on prompt + emitted tokens.  Two
    requests share ticks; a third is a whole page-aligned prefix hit (the
    engine copies the last page before it writes: copy-on-write) and a
    fourth a partial hit with a suffix of its own, as the benchmark's
    probes (benchmark/lib/check.py)."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, prefill_chunk=32)
    assert eng.pool.latent and eng.pool.kv.shape == (3, 65, 16, 128)
    # 128 lanes hold the 40 values of a latent row: ONE leaf, no value pool
    assert eng.pool.kv_pool_bytes() == 3 * 65 * 16 * 128 * 4
    jobs = [(PROMPT_A, 12), (PROMPT_B, 12)]
    reqs = [eng.submit(p, n, top_k=1, termination_id=NEVER) for p, n in jobs]
    eng.run_until_idle()
    later = [(PROMPT_B[:96], 12), (PROMPT_B[:88] + PROMPT_A[:30], 12)]
    for p, n in later:
        reqs.append(eng.submit(p, n, top_k=1, termination_id=NEVER))
        eng.run_until_idle()
    assert eng.prefix_hit_tokens >= 96 + 80 and eng.cow_copies >= 1
    for req in reqs:
        tokens, lps = req.result(timeout=120)
        want = reference_log_probs(params, tokens)[len(req.prompt) - 1:]
        np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=ATOL)
    # what the router did rode the ticks' fetches: rows x top-4 x 2 layers
    assert eng.moe_assignments > 0 and eng.moe_assignments % 8 == 0
    assert 0 < eng.moe_experts_touched <= eng.ticks * 2 * 16
    assert eng.moe_experts_touched < eng.moe_assignments


def test_engine_scored_prompt_and_preemption(model):
    """The scoring chunk (a program of its own, ``[1, rows]`` tokens through
    the same absorbed rows) gives the prompt's log-probabilities, and a
    preempted request resumes through the latent pool to the same
    stream."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, prefill_chunk=32)
    scored = eng.submit(PROMPT_A, 6, top_k=1, termination_id=NEVER,
                        return_log_probs=True)
    eng.run_until_idle()
    tokens, lps = scored.result(timeout=120)
    want = reference_log_probs(params, tokens)
    np.testing.assert_allclose(
        np.asarray(scored.prompt_log_probs), want[:len(PROMPT_A) - 1],
        rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(lps), want[len(PROMPT_A) - 1:],
                               rtol=0, atol=ATOL)

    victim = eng.submit(PROMPT_B, 16, top_k=1, termination_id=NEVER)
    for _ in range(12):
        eng.step()
    assert victim.generated and eng.preempt(victim)
    eng.run_until_idle()
    tokens, lps = victim.result(timeout=120)
    assert eng.preemptions == 1 and len(tokens) == len(PROMPT_B) + 16
    np.testing.assert_allclose(
        np.asarray(lps), reference_log_probs(params, tokens)[len(PROMPT_B) - 1:],
        rtol=0, atol=ATOL)


# ---- the dropless dispatch --------------------------------------------------


def _experts(cfg, key):
    return jax.tree.map(
        lambda a: a, moe.init_moe_params(cfg, key)["experts"])


def _per_token_loop(cfg, experts, x, idx, w):
    fc1, fc2 = experts["fc1"]["kernel"], experts["fc2"]["kernel"]
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for k in range(idx.shape[1]):
            e = int(idx[t, k])
            y = x[t] @ fc1[e, 0] * jax.nn.silu(x[t] @ fc1[e, 1])
            out[t] += float(w[t, k]) * np.asarray(y @ fc2[e], np.float64)
    return out


@pytest.mark.parametrize("load", ["one_row", "router", "all_on_one_expert",
                                  "two_experts_only"])
def test_dropless_dispatch_equals_per_token_loop(load):
    """No token is dropped at any load, from one row to every assignment of
    every row on ONE expert (a capacity of 1.25 would drop 15 of 16)."""
    cfg = joyai_cfg()
    experts = _experts(cfg, jax.random.PRNGKey(5))
    rows = 1 if load == "one_row" else 24
    x = jax.random.normal(jax.random.PRNGKey(6), (rows, 64), jnp.float32)
    rng = np.random.default_rng(2)
    if load in ("one_row", "router"):
        idx = np.stack([rng.permutation(16)[:4] for _ in range(rows)])
    elif load == "all_on_one_expert":
        idx = np.full((rows, 4), 11)      # even a token's four choices
    else:
        idx = rng.choice([3, 12], size=(rows, 4))
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=idx.shape), jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    counts = (idx.reshape(-1, 1) == jnp.arange(16)[None]).sum(0)
    got, ran, dropped = moe.dropless_experts(cfg, experts, x, idx, w,
                                             counts.astype(jnp.float32))
    assert (float(ran), float(dropped)) == (idx.size, 0)   # all held
    np.testing.assert_allclose(
        np.asarray(got), _per_token_loop(cfg, experts, x, idx, w),
        rtol=0, atol=ATOL)
    # the serving tick's form: every layer's experts and the layer meant
    stack = jax.tree.map(lambda a: jnp.stack([a * 0 + 3.0, a, a * 0 - 1.0]),
                         experts)
    stacked, _, _ = moe.dropless_experts(
        cfg, moe.StackedExperts(stack, jnp.asarray(1)), x, idx, w,
        counts.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(got))


def test_selection_bias_changes_the_choice_not_the_weights():
    cfg = joyai_cfg()
    p = moe.init_moe_params(cfg, jax.random.PRNGKey(8))["router"]
    x = jax.random.normal(jax.random.PRNGKey(9), (32, 64), jnp.float32)
    scores = jax.nn.sigmoid(x @ p["kernel"])
    plain_idx, plain_w, _, _ = moe.route(
        cfg, {"kernel": p["kernel"]}, x)
    bias = jnp.zeros((16,)).at[5].set(10.0)       # expert 5 always picked
    idx, w, counts, aux = moe.route(cfg, {**p, "bias": bias}, x)
    assert bool((idx == 5).any(axis=1).all())
    assert not bool((plain_idx == 5).any(axis=1).all())
    # the weights are the scores of the chosen, renormalised and scaled by
    # 2.5: the 10.0 is nowhere in them
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    want = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    assert float(counts[5]) == 32 and float(counts.sum()) == 32 * 4
    assert float(aux[2]) == 32 * 4 and float(aux[3]) == float((counts > 0).sum())
    # softmax, no bias, renormalised (Mixtral) through the same function
    mix = make_config("mixtral", num_layers=1, hidden_size=64, vocab_size=64,
                      num_attention_heads=4, num_experts=16, moe_router_topk=2)
    idx2, w2, _, _ = moe.route(mix, {"kernel": p["kernel"]}, x)
    top = jax.lax.top_k(jax.nn.softmax(x @ p["kernel"]), 2)
    assert bool((idx2 == top[1]).all())
    np.testing.assert_allclose(np.asarray(w2),
                               np.asarray(top[0] / top[0].sum(-1, keepdims=True)),
                               rtol=1e-6)


def test_mixtral_through_the_engine_equals_mixtral_served_alone():
    """The first MoE-through-engine test: top-2 of 8 softmax experts in
    mixed ticks against each request alone on a fresh engine, and against
    the dense single-stream path."""
    cfg = make_config(
        "mixtral", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, vocab_size=VOCAB, num_experts=8,
        moe_router_topk=2, params_dtype="float32", use_flash_attn=False,
        max_position_embeddings=256, seq_length=256, max_batch_slots=4,
        engine_max_seq=256)
    params = init_model_params(cfg, jax.random.PRNGKey(11))

    def make():
        return ContinuousBatchingEngine(cfg, params, prefill_chunk=32)

    greedy = dict(top_k=1, termination_id=NEVER)
    jobs = [(PROMPT_A[:40], 10, greedy), (PROMPT_B[:75], 10, greedy),
            (PROMPT_A[:21], 10, dict(top_k=0, temperature=0.9, seed=5,
                                     termination_id=NEVER))]
    eng = make()
    mixed = parity.run_jobs(eng, jobs)
    assert eng.moe_assignments > 0
    alone = parity.serve_alone(make, jobs)
    parity.assert_same_generations(parity.generations(mixed),
                                   parity.generations(alone))
    assert parity.assert_greedy_match_dense(cfg, params, jobs, mixed) == 2


# ---- what does not carry a one-leaf pool says so at start-up ----------------


def _mesh(**axes):
    from megatron_llm_tpu.core.parallel_state import build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(**axes, data_parallel_size=1, devices=jax.devices()[:n])


@pytest.mark.parametrize("case, sentence", [
    ("int8", "--kv_dtype int8"), ("fp8", "--kv_dtype fp8"),
    ("tp", "tensor-parallel serving"), ("pp", "pipeline-parallel serving"),
    ("spec", "--spec_k"),
    ("handoff_role", "KV handoff"), ("handoff_call", "KV handoff")])
def test_latent_cache_refusals(model, case, sentence):
    cfg, params = model
    kw = {}
    if case in ("int8", "fp8"):
        kw = dict(kv_dtype=case)
    elif case == "tp":
        kw = dict(mesh=_mesh(tensor_model_parallel_size=2))
    elif case == "pp":
        kw = dict(mesh=_mesh(pipeline_model_parallel_size=2))
    elif case == "spec":
        kw = dict(spec_k=2, spec_draft="llama2:num_layers=1")
    with pytest.raises(ValueError, match="ONE latent row") as err:
        if case == "pp":
            # (pp cuts one uniform stack: the config refuses a dense prefix
            # before the engine is reached, so ask the pool's rule itself)
            refuse_unserved(cfg, mesh=kw["mesh"])
        elif case.startswith("handoff"):
            eng = ContinuousBatchingEngine(cfg, params)
            if case == "handoff_role":
                from megatron_llm_tpu.generation.server import MegatronServer

                MegatronServer(eng, role="prefill")
            else:
                eng.export_cached_kv(PROMPT_A)
        else:
            ContinuousBatchingEngine(cfg, params, **kw)
    assert sentence in str(err.value)


def test_flash_kernel_refuses_unequal_widths():
    from megatron_llm_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 4, 192), jnp.float32)
    with pytest.raises(ValueError, match="one head width"):
        flash_attention(q, q, jnp.zeros((1, 128, 4, 128), jnp.float32))


def test_long_probes_compared_at_the_emitted_positions(model, monkeypatch):
    """PERF.md 7(p)(1), the tier-1 case ISSUE 38 asked for here and a
    `benchmark` PR could not add: probes longer than one block of the
    reference's attention (blocks of 32 queries; 72 + 32 and 88 + 32 tokens)
    served through the engine, two of them out of the prefix cache, and
    compared as a run's ``correct`` compares them: the reference's stack on
    prompt + emitted tokens, its head on the emitted positions only
    (``benchmark/lib/check.py`` ``emitted_reference``)."""
    import types

    from benchmark.lib import check

    monkeypatch.setattr(ref_common, "QUERY_BLOCK", 32)
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, prefill_chunk=32)
    probes = check.serve_probes(11, VOCAB, (72, 88), eng.page_size)
    assert [p["name"] for p in probes] == ["alone", "first", "whole_hit",
                                           "part_hit"]
    for group in (probes[:2], probes[2:3], probes[3:]):
        reqs = [eng.submit(p["prompt"], check.PROBE_TOKENS, top_k=1,
                           termination_id=NEVER) for p in group]
        eng.run_until_idle()
        for p, req in zip(group, reqs):
            tokens, lps = req.result(timeout=120)
            p["tokens"], p["logprobs"] = tokens[len(p["prompt"]):], lps
    assert eng.prefix_hit_tokens >= 80 + 64 and eng.cow_copies == 1
    cell = types.SimpleNamespace(config={"reference": "joyai_block"},
                                 model=MODEL)
    want = check.emitted_reference(cell, params, probes)
    got = [lp for p in probes for lp in p["logprobs"]]
    assert len(got) == 4 * check.PROBE_TOKENS
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    # blocks change no number: the plain form reads the same
    monkeypatch.setattr(ref_common, "QUERY_BLOCK", 512)
    whole = check.emitted_reference(cell, params, probes[:1])
    np.testing.assert_allclose(whole, want[:check.PROBE_TOKENS], rtol=0,
                               atol=1e-5)
