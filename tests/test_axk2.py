"""A.X-K2 (ISSUE 67): latent attention under a learned indexer (every query
attends the ``index_topk`` rows its indexer scores best), index keys cached
beside the latent rows under one page id, a gate a head on the attention's
output, low-rank gated norms, a group-limited router over a held share of
the experts — at tiny widths with the real structure (1 dense + 2 expert
layers, 16 experts in 4 groups of which 2 stay, top-4, one shared;
``index_topk`` 16 against contexts of 40-110), seeded random float32
weights, against ``benchmark/reference/axk2_block.py``.

Tolerances as tests/test_joyai.py: program and reference are both float32
and differ in the order of their sums (absorbed against expanded attention,
a gathered list against a mask, a threshold against a sort), so logits and
log-probabilities agree to ``ATOL``.  Both selections are discrete (the
router's experts, the indexer's keys): the router's bias is drawn at std
0.5 so that no choice hangs on a rounding, and the indexer's sets are
compared AS SETS with the reference's at every layer and query.
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import axk2_block
from benchmark.reference import common as ref_common
from megatron_llm_tpu.generation import ContinuousBatchingEngine
from megatron_llm_tpu.generation.pools import memory_kind
from megatron_llm_tpu.models import init_model_params, make_config, moe
from megatron_llm_tpu.models import sparse_mla
from megatron_llm_tpu.models.language_model import (
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.models.transformer import LayerPool, mla_sublayer
from megatron_llm_tpu.ops import kv_quant
from megatron_llm_tpu.ops import sparse_attention as sparse_ops
from megatron_llm_tpu.ops.norms import norm
from megatron_llm_tpu.ops.paged_attention import PagedState

ATOL = 2e-5
VOCAB = 256
TOPK = 16
NEVER = 10 ** 9          # a termination id no token reaches

WIDTHS = dict(
    num_layers=2, hidden_size=64, num_attention_heads=4, ffn_hidden_size=160,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=TOPK,
    gated_norm_rank=4, num_experts=16, moe_router_topk=4,
    moe_ffn_hidden_size=40, moe_n_group=4, moe_topk_group=2,
    vocab_size=VOCAB, params_dtype="float32", use_flash_attn=False,
    max_position_embeddings=512)
ROPE = dict(rope_type="yarn", rope_theta=1000000, factor=2, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=131072)
# the same sizes under the published config's names: what the reference reads
MODEL = dict(num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=24,
             qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6,
             index_n_heads=4, index_head_dim=16, index_topk=TOPK,
             num_experts_per_tok=4, routed_scaling_factor=2.5, n_group=4,
             topk_group=2, rope_parameters=ROPE)


def axk2_cfg(**kw):
    return make_config("axk2", **{**WIDTHS, **kw})


def _with_bias(params, key=7):
    bias = params["layers"]["moe"]["router"]["bias"]
    params["layers"]["moe"]["router"]["bias"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(key), bias.shape, bias.dtype)
    return params


@pytest.fixture(scope="module")
def model():
    cfg = axk2_cfg(max_batch_slots=4, engine_max_seq=256, page_size=16)
    return cfg, _with_bias(init_model_params(cfg, jax.random.PRNGKey(0)))


def reference_log_probs(params, tokens, model=MODEL):
    tokens = jnp.asarray([tokens], jnp.int32)
    logits = axk2_block.logits(params, tokens, model)
    return np.asarray(ref_common.token_log_probs(logits, tokens))[0]


def test_family_and_parameter_tree(model):
    cfg, params = model
    m = cfg.model
    assert (m.mla, m.dense_prefix_layers, m.depth) == (True, 1, 3)
    assert (m.index_topk, m.moe_n_group, m.moe_topk_group) == (TOPK, 4, 2)
    assert m.rope_scaling_type == "yarn" and m.gated_norm
    assert memory_kind(cfg) == "indexed"
    att = params["layers"]["attention"]
    assert att["g_proj"]["kernel"].shape == (2, 64, 4)        # a value a head
    assert att["index_q"]["kernel"].shape == (2, 48, 4 * 16)
    assert att["index_k"]["kernel"].shape == (2, 64, 16)
    assert att["index_w"]["kernel"].shape == (2, 64, 4)
    assert set(att["index_k_norm"]) == {"scale", "bias"}
    for norms in (params["layers"]["input_norm"],
                  params["layers"]["post_norm"],
                  params["dense_layers"]["input_norm"]):
        assert norms["gate_down"].shape[1:] == (64, 4)
        assert norms["gate_up"].shape[1:] == (4, 64)
    assert params["final_norm"]["gate_down"].shape == (64, 4)
    # the latents' norms and the index key's are plain
    assert set(att["q_norm"]) == set(att["kv_norm"]) == {"scale"}
    with pytest.raises(ValueError, match="index_topk"):
        make_config("axk2", **{**WIDTHS, "index_topk": None})
    with pytest.raises(ValueError, match="groups"):
        make_config("axk2", **{**WIDTHS, "moe_n_group": 5})
    big = make_config("a.x-k2")
    bm = big.model
    assert (bm.depth, bm.num_experts, bm.latent_cache_width, bm.index_topk,
            bm.index_n_heads, bm.index_head_dim, bm.moe_n_group,
            bm.moe_topk_group, bm.vocab_size) == (
        61, 256, 576, 2048, 64, 128, 8, 4, 163840)
    assert big.model_name == "axk2"


def test_dense_forward_matches_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, VOCAB)
    out, _ = model_forward(cfg, params, tokens)
    want = axk2_block.logits(params, tokens, MODEL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=ATOL)


@pytest.mark.parametrize("fault", ["half_topk", "no_relu", "no_head_gate",
                                   "no_norm_gate", "no_groups"])
def test_planted_faults_fail_the_reference(model, fault):
    """What the tolerance is for: a reference that selects half as many
    keys, scores without the ReLU, or a program without one of the model's
    own sublayers, lands far outside it."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, VOCAB)
    out, _ = model_forward(cfg, params, tokens)
    broken, bad = MODEL, params
    if fault == "half_topk":
        broken = {**MODEL, "index_topk": TOPK // 2}
    elif fault == "no_relu":
        with mock.patch.object(axk2_block, "index_activation", lambda x: x):
            want = axk2_block.logits(params, tokens, MODEL)
    elif fault == "no_groups":
        broken = {**MODEL, "n_group": 1, "topk_group": 1}
    else:
        bad = jax.tree.map(lambda a: a, params)
        if fault == "no_head_gate":      # sigmoid(0): every gate a half
            for stack in ("layers", "dense_layers"):
                g = bad[stack]["attention"]["g_proj"]
                g["kernel"] = jnp.zeros_like(g["kernel"])
        else:
            for stack in ("layers", "dense_layers"):
                up = bad[stack]["input_norm"]
                up["gate_up"] = jnp.zeros_like(up["gate_up"])
    if fault != "no_relu":
        want = axk2_block.logits(bad, tokens, broken)
    assert float(jnp.abs(out - want).max()) > 100 * ATOL


def _program_sets(cfg, p, x, length):
    """The positions every query of ``x`` [1, s, h] picks in one sublayer,
    by the program's paged pieces on a fresh pool: a list of sets."""
    rope = make_rope_cache(cfg)
    m = cfg.model
    pos = jnp.arange(length, dtype=jnp.int32)
    c_q = norm(x @ p["q_down"]["kernel"], p["q_norm"], 1e-6, True)
    lin = lambda w, t: t @ w["kernel"]                       # noqa: E731
    q_i, k_i, w_i = sparse_mla.index_inputs(cfg, p, x, c_q, rope, pos[None],
                                            lin)
    page, pages = 16, -(-length // 16)
    leaf = jnp.zeros((1, pages + 1, page, m.index_head_dim), jnp.float32)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    leaf = sparse_ops.write_rows(leaf, 0, table[0][pos // page], pos % page,
                                 k_i[0])
    tables = jnp.broadcast_to(table, (length, pages))
    scores = sparse_ops.index_scores(q_i[0], w_i[0], leaf, 0, tables, pos + 1)
    shared = sparse_ops.index_scores(q_i[0], w_i[0], leaf, 0, table, pos + 1)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(scores),
                               rtol=0, atol=1e-6)
    return {"threshold": _sets(scores, pos + 1, m.index_topk),
            "sort": _sorted_sets(scores, pos + 1, m.index_topk)}


def _sets(scores, ctx, k):
    """The program's selection as sets: the mask, and the list made of it."""
    sel = sparse_ops.select_mask(scores, ctx, k)
    idx, valid = sparse_ops.compact(sel, k)
    sets = [set(np.nonzero(r)[0].tolist()) for r in np.asarray(sel)]
    assert sets == [set(np.asarray(i)[np.asarray(v)].tolist())
                    for i, v in zip(idx, valid)]
    # the list is ascending and holds every pick once
    assert all(int(v.sum()) == len(s) for v, s in zip(np.asarray(valid), sets))
    return sets


def _sorted_sets(scores, ctx, k):
    """lax.top_k (of equal values the lower index first): the oracle."""
    _, idx = jax.lax.top_k(scores, min(k, scores.shape[1]))
    return [set(i[i < c].tolist()) for i, c in zip(np.asarray(idx),
                                                   np.asarray(ctx))]


def test_selected_sets_equal_the_references(model):
    """``S_t`` of the program (both forms of the selection, through the
    index keys' paged leaf) equal to the reference's, query by query, and
    the dense forward's mask the same sets."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"]["attention"])
    length = 90
    x = jax.random.normal(jax.random.PRNGKey(3), (1, length, 64), jnp.float32)
    got = _program_sets(cfg, p, x, length)
    tap = []
    with jax.default_matmul_precision("highest"):
        axk2_block.attention(p, x, MODEL, tap)
    want = np.asarray(tap[0])[0]                              # [q, k]
    for t in range(length):
        ref = set(np.nonzero(want[t])[0].tolist())
        assert len(ref) == min(t + 1, TOPK)
        assert got["threshold"][t] == ref == got["sort"][t], t
    rope = make_rope_cache(cfg)
    pos = jnp.arange(length)[None]
    c_q = norm(x @ p["q_down"]["kernel"], p["q_norm"], 1e-6, True)
    bias = sparse_mla.dense_bias(cfg, *sparse_mla.index_inputs(
        cfg, p, x, c_q, rope, pos, lambda w, t: t @ w["kernel"]))
    np.testing.assert_array_equal(np.asarray(bias[0, 0] == 0.0), want)


def test_selection_ties_go_to_the_earlier_position():
    scores = jnp.asarray([[0.5, 2.0, 0.5, 0.5, -1.0, 0.5, 9.0, 9.0]] * 3)
    ctx = jnp.asarray([8, 6, 3], jnp.int32)
    scores = jnp.where(jnp.arange(8)[None] < ctx[:, None], scores, -jnp.inf)
    want = [{6, 7, 1, 0}, {1, 0, 2, 3}, {0, 1, 2}]
    assert _sets(scores, ctx, 4) == want == _sorted_sets(scores, ctx, 4)
    allowed = jnp.arange(8)[None] < ctx[:, None]
    keep = sparse_mla.selected(scores, allowed, 4)
    assert [set(np.nonzero(r)[0].tolist()) for r in np.asarray(keep)] == [
        {6, 7, 1, 0}, {1, 0, 2, 3}, {0, 1, 2}]


@pytest.mark.parametrize("length", [TOPK, TOPK + 1])
def test_sparse_rows_equal_the_expanded_form(model, length):
    """One sublayer: the expanded form under the selection's mask on a
    whole sequence against the paged form fed the same tokens as single
    rows (layer 1 of a 3-layer pool, pages out of order), at a context of
    exactly ``index_topk`` (every key attended, nothing selected) and one
    more (the first query that drops a key)."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["layers"]["attention"])
    s, page, pages = length, 16, 9
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, 64), jnp.float32)
    rope = make_rope_cache(cfg)
    want, none = mla_sublayer(cfg, p, x, rope, jnp.arange(s)[None], None)
    assert none is None
    pool = kv_quant.IndexedLatent(
        rows=jnp.full((3, pages, page, 128), 7.0, jnp.float32),
        index=jnp.full((3, pages, page, 16), 7.0, jnp.float32))
    table = jnp.asarray([[5, 2, 8, 0]], jnp.int32)
    got, new_pool = mla_sublayer(
        cfg, p, x[0][:, None, :], rope, jnp.arange(s)[:, None], None,
        kv_cache=LayerPool(pool, jnp.asarray(1)),
        paged=PagedState(table, jnp.arange(s, dtype=jnp.int32),
                         jnp.full((s,), 64, jnp.int32),
                         jnp.zeros((s,), jnp.int32)))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want[0]),
                               rtol=0, atol=ATOL)
    # a latent row and an index key a token in layer 1's pages 5, 2;
    # nothing anywhere else, in either leaf
    assert float(jnp.abs(new_pool.rows[1, 5, :, :40]).max()) < 7.0
    assert float(jnp.abs(new_pool.index[1, 5]).max()) < 7.0
    for leaf in new_pool:
        untouched = leaf.at[1, jnp.asarray([5, 2])].set(7.0)
        assert bool((untouched == 7.0).all())


def test_a_tile_of_one_table_equals_rows_of_tables_of_their_own():
    """The tile's two paths give the same numbers: 70 rows (three tiles,
    the last part dead) that all name ONE table (a block of its keys read
    once, the selection a mask, the softmax carried across blocks) against
    the same rows each naming a copy of it (every row its own gather, a
    list of picked positions, the dense form)."""
    key = jax.random.PRNGKey(9)
    rows, pages, page, heads, dim, n, lanes, vw = 70, 12, 16, 4, 16, 4, 128, 32
    width, topk = 10, 24
    k = [jax.random.fold_in(key, i) for i in range(6)]
    index_leaf = jax.random.normal(k[0], (2, pages * rows + 1, page, dim))
    latent_leaf = jax.random.normal(k[1], (2, pages * rows + 1, page, lanes))
    q_i = jax.random.normal(k[2], (rows, heads, dim))
    w_i = jax.random.normal(k[3], (rows, heads))
    q_abs = jax.random.normal(k[4], (rows, n, lanes))
    ctx = jax.random.randint(k[5], (rows,), 1, width * page).at[5].set(0)
    one = jnp.arange(1, width + 1, dtype=jnp.int32)[None]
    # copies of the table's pages, a set a row, so that no two rows share
    own = 1 + width + jnp.arange(rows * width, dtype=jnp.int32).reshape(
        rows, width)
    for leaf in ("index_leaf", "latent_leaf"):
        a = locals()[leaf]
        a = a.at[:, own.reshape(-1)].set(
            jnp.tile(a[:, one[0]], (1, rows, 1, 1)))
        if leaf == "index_leaf":
            index_leaf = a
        else:
            latent_leaf = a
    call = lambda tables, index: sparse_ops.sparse_attention(  # noqa: E731
        q_i, w_i, q_abs, index_leaf, latent_leaf, 1, tables, index, ctx,
        topk, 0.2, vw)
    shared = call(one, jnp.zeros((rows,), jnp.int32))
    lone = call(own, jnp.arange(rows, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(shared), np.asarray(lone), rtol=0,
                               atol=ATOL)
    assert float(jnp.abs(shared[5]).max()) == 0.0            # the dead row
    assert float(jnp.abs(shared[6]).max()) > 0.0


PROMPT_A = np.random.default_rng(0).integers(0, VOCAB, 70).tolist()
PROMPT_B = np.random.default_rng(1).integers(0, VOCAB, 100).tolist()


def test_engine_matches_reference_through_the_indexed_pool(model):
    """Chunked prefill, then decode, through the ragged tick: every row
    writes its latent row and its index key, sweeps, selects and attends
    its picked rows; the log-probability the engine reports for every token
    it emits against the reference's full forward.  Two requests share
    ticks; a third is a whole page-aligned prefix hit (copy-on-write of
    BOTH leaves of the last page) and a fourth a partial hit with a suffix
    of its own: their index keys come out of cached pages."""
    cfg, params = model
    before = eng_metrics()       # the registry is the process's
    eng = ContinuousBatchingEngine(cfg, params, prefill_chunk=32)
    kv = eng.pool.kv
    assert isinstance(kv, kv_quant.IndexedLatent) and eng.pool.latent
    assert kv.rows.shape == (3, 65, 16, 128) and kv.index.shape == (
        3, 65, 16, 16)
    assert eng.pool.kv_pool_bytes() == 3 * 65 * 16 * (128 + 16) * 4
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
    # the counters: every live row past 16 keys selected, in 3 layers
    after = eng_metrics()
    grew = lambda name: after.get(name, 0.0) - before.get(name, 0.0)  # noqa: E731
    rows = grew("mlt_engine_sparse_rows_total")
    assert rows > 0
    assert grew("mlt_engine_sparse_keys_attended_total") == 3 * TOPK * rows
    assert grew("mlt_engine_sparse_keys_scored_total") > 3 * TOPK * rows
    assert grew("mlt_engine_paged_walks_total") == 0.0
    assert grew("mlt_engine_paged_rows_total") >= rows


def eng_metrics():
    from megatron_llm_tpu.observability import registry as obs_registry

    out = {}
    for line in obs_registry.get_registry().render().splitlines():
        if line and not line.startswith("#") and "{" not in line:
            name, value = line.rsplit(" ", 1)
            out[name] = float(value)
    return out


def test_engine_scored_prompt_and_preemption(model):
    """The scoring chunk (``[1, rows]`` tokens through the same sparse
    rows) gives the prompt's log-probabilities, and a preempted request
    resumes through the indexed pool to the same stream."""
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
        np.asarray(lps),
        reference_log_probs(params, tokens)[len(PROMPT_B) - 1:],
        rtol=0, atol=ATOL)


def test_group_limited_router_matches_reference_on_1000_rows():
    cfg = axk2_cfg()
    key = jax.random.PRNGKey(11)
    router = {"kernel": jax.random.normal(key, (64, 16)) * 0.3,
              "bias": 0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                              (16,))}
    x = jax.random.normal(jax.random.fold_in(key, 2), (1000, 64))
    idx, w, counts, _ = moe.route(cfg, router, x)
    want = np.asarray(axk2_block.router_weights(router, x, MODEL))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # every row's four experts lie in two of the four groups of four
    groups = np.asarray(idx) // 4
    assert max(len(set(g)) for g in groups.tolist()) <= 2
    assert float(counts.sum()) == 4000
    # and the limit bites: without groups other experts are chosen
    plain = np.asarray(axk2_block.router_weights(
        router, x, {**MODEL, "n_group": 1, "topk_group": 1}))
    assert ((plain > 0) != (want > 0)).any(axis=1).mean() > 0.2


def test_shares_of_the_experts_sum_to_the_uncut_layer():
    """Four chips' shares of four experts each (the shared expert counted
    once) sum to the layer that holds all sixteen."""
    whole = axk2_cfg()
    key = jax.random.PRNGKey(5)
    p = moe.init_moe_params(whole, key)
    p["router"]["bias"] = 0.5 * jax.random.normal(
        jax.random.fold_in(key, 1), p["router"]["bias"].shape)
    x = jax.random.normal(jax.random.fold_in(key, 2), (3, 50, 64))
    full, _ = moe.moe_sublayer(whole, p, x)
    shared = moe.moe_sublayer(
        whole, {**p, "experts": jax.tree.map(jnp.zeros_like, p["experts"])},
        x)[0]
    total = jnp.zeros_like(full)
    for first in range(0, 16, 4):
        share_cfg = axk2_cfg(moe_experts_held=4, moe_first_held_expert=first,
                             moe_capacity_factor=4.0)
        held = {**p, "experts": jax.tree.map(
            lambda a: a[first:first + 4], p["experts"])}
        total = total + moe.moe_sublayer(share_cfg, held, x)[0] - shared
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full),
                               rtol=0, atol=ATOL)


def test_held_share_through_the_engine_matches_reference():
    """One chip's share (experts 4-7 of 16) served through the engine
    against the reference on the same held stacks."""
    cfg = axk2_cfg(max_batch_slots=2, engine_max_seq=128, page_size=8,
                   moe_experts_held=4, moe_first_held_expert=4,
                   moe_capacity_factor=4.0)
    params = _with_bias(init_model_params(cfg, jax.random.PRNGKey(2)))
    assert params["layers"]["moe"]["experts"]["fc1"]["kernel"].shape[1] == 4
    eng = ContinuousBatchingEngine(cfg, params, prefill_chunk=16)
    req = eng.submit(PROMPT_A[:40], 8, top_k=1, termination_id=NEVER)
    eng.run_until_idle()
    tokens, lps = req.result(timeout=120)
    want = reference_log_probs(
        params, tokens, {**MODEL, "first_held_expert": 4})[39:]
    np.testing.assert_allclose(np.asarray(lps), want, rtol=0, atol=ATOL)
