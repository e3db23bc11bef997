"""Continuous-batching engine + paged KV cache tests (ISSUE 1).

Gates: (1) the paged decode path is numerically IDENTICAL to the dense-cache
decode path — bitwise for greedy tokens/logits on CPU; (2) the block-table
allocator never leaks or double-books pages under churn; (3) per-slot
sampling is a function of (request seed, step) alone, not slot placement;
(4) the compiled-program cache keys on config CONTENT, not object identity.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.generation import (
    ContinuousBatchingEngine,
    generate_tokens,
)
from megatron_llm_tpu.generation.generation import (
    _JIT_CACHE,
    cached_jit,
    clear_jit_cache,
    config_fingerprint,
    init_kv_caches,
)
from megatron_llm_tpu.generation.sampling import (
    modify_logits_for_top_k_filtering,
    modify_logits_for_top_p_filtering,
    sample,
    sample_per_slot,
)
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.models.language_model import (
    _compute_dtype,
    make_rope_cache,
    model_forward,
)
from megatron_llm_tpu.ops.paged_attention import (
    PagedState,
    paged_attention_decode,
)
from tools import tpu_kernel_check as kernel_check

VOCAB = 67


class ToyTokenizer:
    eod = 0
    bos = 1
    vocab_size = VOCAB

    def tokenize(self, text):
        return [2 + (ord(c) % (VOCAB - 2)) for c in text]

    def detokenize(self, ids):
        return "".join(chr(97 + (i % 26)) for i in ids if i >= 2)


@pytest.fixture(scope="module")
def toy_model():
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=2, ffn_hidden_size=128, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False,
    )
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Kernel / op level
# ---------------------------------------------------------------------------


# the kernels at their smallest cases, against the gather path one call at a
# time: tests/test_paged_kernel_cases.py (a file of its own, so that another
# worker of the tier-1 run takes it: it is half of what this file took)


@pytest.mark.parametrize("rows", [16, 128], ids=["two-matmuls", "stacked"])
def test_bf16_ulps_tells_a_plain_cast_of_the_probabilities(rows):
    """The measure itself: softmax(s) @ v in float32 against the same with
    the probabilities split into two bf16 halves (the kernel's
    ``_weighted_values``, in both of its forms) and with a plain cast."""
    from megatron_llm_tpu.ops.pallas.paged_attention import (
        STACK_ROWS,
        _weighted_values,
    )

    assert 16 < STACK_ROWS <= 128
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(rows, 512)) * 2, jnp.float32)
    v = jnp.asarray(rng.normal(size=(512, 128)), jnp.bfloat16)
    p = jax.nn.softmax(s, axis=-1)
    exact = p @ v.astype(jnp.float32)
    differ, ulps = kernel_check.bf16_ulps(
        _weighted_values(p, v).astype(jnp.bfloat16), exact)
    assert differ < 0.01 and ulps <= 1.0, (differ, ulps)
    cast = jnp.dot(p.astype(jnp.bfloat16), v,
                   preferred_element_type=jnp.float32)
    differ, _ = kernel_check.bf16_ulps(cast.astype(jnp.bfloat16), exact)
    assert differ > 0.1, differ
    # float32 values: the product as it is
    assert np.array_equal(_weighted_values(p, v.astype(jnp.float32)),
                          jnp.dot(p, v.astype(jnp.float32)))


@pytest.mark.parametrize("q_dtype,page_dtype,quantized,operand", [
    (jnp.bfloat16, jnp.bfloat16, False, jnp.bfloat16),
    (jnp.bfloat16, jnp.int8, True, jnp.bfloat16),
    (jnp.bfloat16, jnp.float8_e4m3fn, True, jnp.bfloat16),
    (jnp.float32, jnp.bfloat16, False, jnp.float32),
    (jnp.float32, jnp.float32, False, jnp.float32),
    (jnp.float32, jnp.int8, True, jnp.float32),
    (jnp.bfloat16, jnp.float32, False, jnp.float32),
])
def test_paged_kernel_operand_dtype_follows_the_call(
        q_dtype, page_dtype, quantized, operand):
    """What decides the matmuls' operands is the dtypes the call observes:
    bf16 only where it rounds nothing; a float32 query keeps float32."""
    from megatron_llm_tpu.ops.pallas.paged_attention import _operand_dtype

    assert _operand_dtype(q_dtype, page_dtype, quantized) == operand


@pytest.mark.parametrize("rows,shared,live", [
    # (table, position, horizon) a row; a tile of 8 rows of one table at
    # consecutive positions is one run, whatever their horizons' buckets
    ([(3, 60 + i, 64 if i < 4 else 128) for i in range(8)], [True], [8]),
    # decode rows: a table each
    ([(1 + i, 500, 512) for i in range(8)], [False], [8]),
    # one request's rows end and the next one's begin inside the tile
    ([(2, 16 + i, 64) for i in range(5)] + [(3, 0, 64), (3, 1, 64),
                                            (3, 2, 64)], [False], [8]),
    # a dead row (table 0, horizon 0) breaks a run; it costs no walk
    ([(2, 16 + i, 64) for i in range(7)] + [(0, 0, 0)], [False], [7]),
    # a gap in the positions, and a horizon that cuts below the position
    ([(2, i if i < 4 else i + 1, 64) for i in range(8)], [False], [8]),
    ([(2, 64 + i, 128 if i else 64) for i in range(8)], [False], [8]),
    # 64 slots' worth: two tiles of decode rows, three dead, then a
    # 16-row chunk = two runs; 11 rows more fill a tile and start one
    ([(1 + i, 99, 128) for i in range(13)] + [(0, 0, 0)] * 3
     + [(40, 128 + i, 192) for i in range(16)]
     + [(41, 7 + i, 64) for i in range(11)],
     [False, False, True, True, True, False], [8, 5, 8, 8, 8, 3]),
])
def test_tile_runs_rule(rows, shared, live):
    """The grouping rule by hand: which tiles are one run, and how many
    walks the rest cost; the same from numpy and from traced arrays."""
    from megatron_llm_tpu.ops.pallas.paged_attention import TILE, tile_runs

    assert TILE == 8
    cols = [np.array(c, np.int32) for c in zip(*rows)]
    for arrays in (cols, [jnp.asarray(c) for c in cols]):
        got_shared, got_live = tile_runs(*arrays)
        assert np.asarray(got_shared).tolist() == shared
        assert np.asarray(got_live).tolist() == live
    assert isinstance(tile_runs(*cols)[0], np.ndarray)


def test_engine_counts_rows_and_walks_by_the_kernels_rule(toy_model):
    """mlt_engine_paged_rows_total / _walks_total: a launched tick's live
    rows and the page walks they cost under ``tile_runs``, from the plan
    alone.  8 slots, so a prompt's rows start on a tile: a prompt of 40
    tokens fills 48 rows (whole pages) = 6 runs of 8; every decode row is
    a walk of its own."""
    from megatron_llm_tpu.observability import registry as registry_mod

    reg = registry_mod.get_registry()

    def read():
        return (reg.counter("mlt_engine_paged_rows_total").value,
                reg.counter("mlt_engine_paged_walks_total").value,
                reg.counter("mlt_engine_ticks_total").value)

    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=8, max_seq=128)
    rows0, walks0, ticks0 = read()
    req = eng.submit([5 + i % 50 for i in range(40)], 6, top_k=1,
                     termination_id=10 ** 9)
    eng.run_until_idle()
    req.result(timeout=5)
    rows, walks, ticks = (b - a for a, b in zip((rows0, walks0, ticks0),
                                                read()))
    decode_rows = rows - 48
    assert 0 < decode_rows <= ticks
    assert walks == 6 + decode_rows
    # a second prompt beside a decoding one, 20 tokens = 32 rows: 4 runs
    rows0, walks0, _ = read()
    eng.submit([7] * 3, 8, top_k=1, termination_id=10 ** 9)
    eng.step(), eng.step(), eng.step()
    rows1, walks1, _ = read()
    eng.submit([9 + i % 40 for i in range(20)], 2, top_k=1,
               termination_id=10 ** 9)
    eng.run_until_idle()
    rows2, walks2, _ = read()
    assert (rows2 - rows1) - (walks2 - walks1) == 32 - 4
    assert rows1 - rows0 == walks1 - walks0 + 16 - 2   # a page of 3 tokens


def test_dense_vs_paged_model_forward_bitwise(toy_model):
    """Dense-cache decode and paged-cache decode produce BITWISE identical
    logits at every step (greedy), pool pages deliberately non-contiguous."""
    cfg, params = toy_model
    rope = make_rope_cache(cfg)
    b, S, page = 2, 32, 8
    maxp = S // page
    L = cfg.model.num_layers
    nkv, d = cfg.model.num_attention_heads_kv, cfg.model.kv_channels
    tokens = np.random.RandomState(0).randint(2, VOCAB, (b, S)).astype(np.int32)
    prompt_len = 7

    caches = init_kv_caches(cfg, b, S, _compute_dtype(cfg))
    logits_d, caches = model_forward(
        cfg, params, jnp.asarray(tokens[:, :prompt_len]),
        position_ids=jnp.arange(prompt_len)[None, :].repeat(b, 0),
        rope_cache=rope, kv_caches=caches, cache_index=jnp.int32(0))

    # interleave the two rows' pages so the block tables are non-trivial
    from megatron_llm_tpu.ops import kv_quant

    P = 1 + b * maxp
    pool = kv_quant.make_kv_pool(L, P, page, nkv, d, "bf16", jnp.float32)
    bt = np.asarray([[1 + 2 * j for j in range(maxp)],
                     [2 + 2 * j for j in range(maxp)]], np.int32)
    ck, cv = (c.reshape(L, b * maxp, page, nkv, d) for c in caches)
    # the dense cache's tokens, in the pool's own row
    pool = pool.at[:, bt.reshape(-1)].set(
        kv_quant.pack_kv(ck, cv).reshape(L, b * maxp, page, -1))
    bt = jnp.asarray(bt)

    tok = jnp.argmax(logits_d[:, -1, :VOCAB], -1).astype(jnp.int32)
    pos = prompt_len
    for _ in range(12):
        ld, caches = model_forward(
            cfg, params, tok[:, None],
            position_ids=jnp.full((b, 1), pos, jnp.int32),
            rope_cache=rope, kv_caches=caches, cache_index=jnp.int32(pos))
        lp, pool = model_forward(
            cfg, params, tok[:, None],
            position_ids=jnp.full((b, 1), pos, jnp.int32),
            rope_cache=rope, kv_caches=pool,
            paged=PagedState(bt, jnp.full((b,), pos, jnp.int32)))
        assert bool(jnp.all(ld == lp)), f"logits diverged at position {pos}"
        tok = jnp.argmax(ld[:, -1, :VOCAB], -1).astype(jnp.int32)
        pos += 1


@pytest.mark.parametrize("family,n,nkv,d", [
    ("llama2", 4, 2, 16), ("falcon", 2, 1, 64), ("llama2", 4, 4, 128)],
    ids=["gqa2x16", "mqa1x64", "mha4x128"])
def test_pool_logical_view_is_the_dense_cache(family, n, nkv, d):
    """Whatever the physical row, the pool's LOGICAL view ``(layer, page,
    offset, head, d)`` holds what the dense incremental cache holds for the
    same tokens: a chunk prefilled through a scattered block table reads
    back through ops/kv_quant (the owner of the row) and through
    ``PagedKVPool.logical_kv`` as the dense cache's keys and values."""
    from megatron_llm_tpu.generation.pools import PagedKVPool
    from megatron_llm_tpu.ops import kv_quant

    cfg = make_config(
        family, num_layers=2, hidden_size=n * d, num_attention_heads=n,
        num_attention_heads_kv=nkv, ffn_hidden_size=64, seq_length=64,
        max_position_embeddings=64, vocab_size=VOCAB, hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype="float32", use_flash_attn=False)
    params = init_model_params(cfg, jax.random.PRNGKey(1))
    rope = make_rope_cache(cfg)
    page, s = 8, 24
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(2, VOCAB, (1, s)), jnp.int32)
    _, (ck, cv) = model_forward(
        cfg, params, tokens, position_ids=jnp.arange(s)[None],
        rope_cache=rope, kv_caches=init_kv_caches(cfg, 1, s, jnp.float32),
        cache_index=jnp.int32(0))

    pool = PagedKVPool(cfg, num_pages=9, page_size=page)
    L = cfg.model.num_layers
    assert pool.kv.shape == (L, 9, page, 2 * nkv * d)   # the physical row
    table = [7, 2, 5]
    _, pool.kv = model_forward(
        cfg, params, tokens, position_ids=jnp.arange(s)[None],
        rope_cache=rope, kv_caches=pool.kv,
        paged=PagedState(jnp.asarray([table], jnp.int32),
                         jnp.zeros((1,), jnp.int32)))
    lk, lv = pool.logical_kv(table)
    assert lk.shape == lv.shape == (L, 3, page, nkv, d)
    np.testing.assert_allclose(
        lk.reshape(L, s, nkv, d), np.asarray(ck)[:, 0], rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        lv.reshape(L, s, nkv, d), np.asarray(cv)[:, 0], rtol=0, atol=1e-6)
    # the same through the owner module's gather, a layer at a time
    for layer in range(L):
        heads = kv_quant.dequant_gather(
            pool.kv, jnp.asarray([table]), d, layer=jnp.int32(layer))
        gk, gv = kv_quant.split_kv(heads)
        np.testing.assert_array_equal(np.asarray(gk)[0], lk[layer].reshape(
            s, nkv, d))
        np.testing.assert_array_equal(np.asarray(gv)[0], lv[layer].reshape(
            s, nkv, d))
    # pages nobody wrote stay zero
    zk, zv = pool.logical_kv([1, 3, 4, 6, 8])
    assert not zk.any() and not zv.any()


# ---------------------------------------------------------------------------
# Engine level
# ---------------------------------------------------------------------------


def test_engine_greedy_matches_generate_tokens(toy_model):
    """Engine greedy decode == the sequential dense generate_tokens path."""
    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=4, max_seq=128)
    prompt = [2 + i % 60 for i in range(10)]
    req = eng.submit(prompt, 8, top_k=1, termination_id=10 ** 9)
    eng.run_until_idle()
    toks, _ = req.result(timeout=5)

    S = 64
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :10] = prompt
    res = generate_tokens(
        cfg, params, tokens, np.array([10], np.int32), 18,
        prefill_len=8, termination_id=10 ** 9,
        sample_key=jax.random.PRNGKey(0), top_k=1)
    np.testing.assert_array_equal(
        np.asarray(toks[10:]), np.asarray(res.tokens)[0, 10:18])


def test_engine_logprobs_match_dense_score(toy_model):
    """Engine per-token log-probs == teacher-forced rescoring of the final
    sequence (the dense path's own consistency contract)."""
    from megatron_llm_tpu.generation.generation import score_tokens

    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=2, max_seq=128)
    prompt = [3, 4, 5, 6, 7, 8]
    req = eng.submit(prompt, 10, top_k=1, termination_id=10 ** 9,
                     return_log_probs=True)
    eng.run_until_idle()
    toks, gen_lp = req.result(timeout=5)
    full = np.asarray(toks, np.int32)[None, :]
    lp_score = np.asarray(score_tokens(cfg, params, jnp.asarray(full)))[0]
    lp_engine = np.asarray(req.prompt_log_probs + gen_lp)
    np.testing.assert_allclose(lp_engine, lp_score[: len(lp_engine)],
                               atol=2e-4, rtol=2e-4)


def test_engine_sampling_slot_invariant(toy_model):
    """A seeded sampled request generates the SAME tokens whether it runs
    alone or alongside other requests in different slots — per-slot keys are
    (seed, step) functions, not (slot, tick)."""
    cfg, params = toy_model
    prompt = [5, 9, 13, 17]
    kw = dict(temperature=0.8, top_p=0.9, seed=123, termination_id=10 ** 9)

    eng1 = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                    max_slots=1, max_seq=128)
    r1 = eng1.submit(prompt, 12, **kw)
    eng1.run_until_idle()

    eng2 = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                    max_slots=4, max_seq=128)
    # fill other slots with competing greedy traffic first so the seeded
    # request lands in a later slot
    others = [eng2.submit([7 + i] * 3, 15, top_k=1, termination_id=10 ** 9)
              for i in range(3)]
    r2 = eng2.submit(prompt, 12, **kw)
    eng2.run_until_idle()
    for o in others:
        o.result(timeout=5)

    t1, _ = r1.result(timeout=5)
    t2, _ = r2.result(timeout=5)
    assert t1 == t2


def test_engine_early_termination_and_page_return(toy_model):
    """Termination id stops a row early; its pages return to the pool while
    other rows keep decoding."""
    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=2, max_seq=128)
    # find the first greedy token, then use it as the termination id
    probe = eng.submit([3, 3, 3, 3], 1, top_k=1, termination_id=10 ** 9)
    eng.run_until_idle()
    first_tok = probe.result(timeout=5)[0][-1]

    short = eng.submit([3, 3, 3, 3], 50, top_k=1, termination_id=first_tok)
    long_ = eng.submit([9, 9, 9, 9], 30, top_k=1, termination_id=10 ** 9)
    eng.run_until_idle()
    t_short, _ = short.result(timeout=5)
    t_long, _ = long_.result(timeout=5)
    assert len(t_short) == 5  # stopped on the first generated token
    assert len(t_long) == 34  # ran to its budget
    # all refs returned; retired prompts may stay cached-idle for reuse
    assert int(eng.pool.refcounts.sum()) == 0
    assert (eng.pool.num_free + len(eng.pool.cached)
            == eng.pool.num_pages - 1)


def test_block_table_alloc_free_stress(toy_model):
    """Churn a deliberately tiny pool: requests queue behind page pressure,
    pages are never double-booked across active slots, and the pool is whole
    when the queue drains."""
    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=3, page_size=16, num_pages=13,
                                   max_seq=128)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(17):
        plen = int(rng.integers(1, 40))
        gen_len = int(rng.integers(1, 30))
        reqs.append(eng.submit([2 + int(x) for x in rng.integers(0, 60, plen)],
                               gen_len, top_k=1, termination_id=10 ** 9))

    total = eng.pool.num_pages - 1
    steps = 0
    while True:
        n = eng.step()
        steps += 1
        held = [p for r in eng._slots if r is not None for p in r._mem[0].pages]
        assert all(p != 0 for p in held), "null page allocated"
        # refcount-exact accounting (the PR-5 three-state page model):
        # every page is free XOR referenced XOR cached-idle, and refcounts
        # equal the number of block tables holding the page
        from collections import Counter

        holders = Counter(held)
        free = set(eng.pool._free)
        for p in range(1, eng.pool.num_pages):
            assert eng.pool.refcounts[p] == holders.get(p, 0), \
                f"page {p} refcount drift"
            if p in free:
                assert eng.pool.refcounts[p] == 0 and p not in eng.pool.cached
        cached_idle = sum(1 for p in eng.pool.cached
                          if eng.pool.refcounts[p] == 0)
        distinct_held = len(holders)
        assert distinct_held + eng.pool.num_free + cached_idle == total, \
            "pages leaked"
        if n == 0 and not eng._queue:
            break
        assert steps < 5000
    for r in reqs:
        toks, _ = r.result(timeout=5)
        assert len(toks) == len(r.prompt) + len(r.generated)
        assert 1 <= len(r.generated) <= r.max_new_tokens
    # drained: nothing referenced; pages are either free or cached-idle
    # (reusable by the next prompt, reclaimable under pressure)
    assert int(eng.pool.refcounts.sum()) == 0
    assert eng.pool.num_free + len(eng.pool.cached) == total


def test_engine_rejects_oversized_request(toy_model):
    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="longer than allowed"):
        eng.submit(list(range(2, 60)), 32)


def test_engine_concurrent_submitters_share_ticks(toy_model):
    """Requests submitted from many threads share decode ticks: total ticks
    is far below the serialized tick count (the >= 3x batching claim the
    decode bench quantifies)."""
    cfg, params = toy_model
    eng = ContinuousBatchingEngine(cfg, params, ToyTokenizer(),
                                   max_slots=8, max_seq=128)
    reqs = [None] * 8

    def submit(i):
        reqs[i] = eng.submit([2 + i, 3 + i, 4 + i], 12, top_k=1,
                             termination_id=10 ** 9)

    threads = [threading.Thread(target=submit, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.run_until_idle()
    total_generated = 0
    for r in reqs:
        toks, _ = r.result(timeout=5)
        total_generated += len(r.generated)
    assert total_generated == 8 * 12
    # serialized decoding would need one tick per generated token
    assert eng.ticks <= 2 * 12 < total_generated


# ---------------------------------------------------------------------------
# Per-slot sampler
# ---------------------------------------------------------------------------


def test_sample_per_slot_matches_static_filters():
    """Row-wise dynamic top-k/top-p filtering == the static single-config
    filters sample() uses, and greedy rows == sample()'s greedy branch."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)) * 3, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4))
    top_k = jnp.asarray([1, 5, 0, 0], jnp.int32)
    top_p = jnp.asarray([0.0, 0.0, 0.7, 0.0], jnp.float32)
    temp = jnp.ones((4,), jnp.float32)

    out = sample_per_slot(keys, logits, top_k=top_k, top_p=top_p,
                          temperature=temp)
    # row 0 greedy == sample() greedy
    assert int(out[0]) == int(sample(None, logits[:1], top_k=1)[0])
    # row 1: token must survive the static top-5 filter
    filt_k = modify_logits_for_top_k_filtering(logits[1:2], 5)
    assert float(filt_k[0, int(out[1])]) > -1e9
    # row 2: token must survive the static top-p filter
    filt_p = modify_logits_for_top_p_filtering(logits[2:3], 0.7)
    assert float(filt_p[0, int(out[2])]) > -1e9
    # per-row keys: same row inputs + same key -> same sample regardless of
    # the rest of the batch
    solo = sample_per_slot(keys[1:2], logits[1:2], top_k=top_k[1:2],
                           top_p=top_p[1:2], temperature=temp[1:2])
    assert int(solo[0]) == int(out[1])


def test_sample_per_slot_temperature_is_ignored_for_greedy():
    logits = jnp.asarray([[0.1, 0.9, 0.5]])
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(1))
    out = sample_per_slot(keys, logits,
                          top_k=jnp.asarray([1]), top_p=jnp.asarray([0.0]),
                          temperature=jnp.asarray([0.01]))
    assert int(out[0]) == 1


def test_sample_per_slot_temperature_to_zero_approaches_greedy():
    """temperature -> 0 collapses the categorical onto the argmax: every
    sampled row must equal the greedy pick whatever its key (the edge the
    speculative verify's acceptance distributions inherit)."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(6, 32)) * 2, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(6))
    out = sample_per_slot(
        keys, logits, top_k=jnp.zeros((6,), jnp.int32),
        top_p=jnp.zeros((6,), jnp.float32),
        temperature=jnp.full((6,), 1e-4, jnp.float32))
    assert np.array_equal(np.asarray(out),
                          np.asarray(jnp.argmax(logits, axis=-1)))


def test_sample_per_slot_top_k_1_is_exact_argmax():
    """top_k=1 rows are EXACTLY argmax over the vocab-masked logits — no
    key dependence, no temperature, padding never wins.  Greedy
    speculative acceptance compares against this value bitwise."""
    rng = np.random.default_rng(4)
    logits = np.asarray(rng.normal(size=(4, 32)) * 2, np.float32)
    logits[:, 30:] = 50.0  # padding region would win without the mask
    logits = jnp.asarray(logits)
    outs = []
    for seed in (0, 7):
        keys = jax.vmap(jax.random.PRNGKey)(seed + jnp.arange(4))
        outs.append(np.asarray(sample_per_slot(
            keys, logits, top_k=jnp.ones((4,), jnp.int32),
            top_p=jnp.zeros((4,), jnp.float32),
            temperature=jnp.asarray([1.0, 0.2, 5.0, 1.0]),
            vocab_size=30)))
    assert np.array_equal(outs[0], outs[1])  # keys are irrelevant
    assert np.all(outs[0] < 30)              # padding masked
    masked = jnp.where(jnp.arange(32)[None, :] >= 30, -1e10, logits)
    assert np.array_equal(outs[0], np.asarray(jnp.argmax(masked, axis=-1)))


def test_sample_per_slot_per_row_key_independence_under_fold_in():
    """The engine derives row keys as fold_in(request_key, step): rows
    sharing LOGITS but folded with different data must draw independently,
    the same (key, data) pair must redraw identically wherever the row
    sits, and reusing a consumed key reproduces the draw — the reuse
    hazard the speculative verify avoids with disjoint fold_in streams
    (graftcheck rng-key-reuse)."""
    rng = np.random.default_rng(5)
    row = rng.normal(size=(1, 64)).astype(np.float32)
    logits = jnp.asarray(np.repeat(row, 8, axis=0))
    base = jax.random.PRNGKey(42)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(8))
    kw = dict(top_k=jnp.zeros((8,), jnp.int32),
              top_p=jnp.zeros((8,), jnp.float32),
              temperature=jnp.full((8,), 1.5, jnp.float32))
    out = np.asarray(sample_per_slot(keys, logits, **kw))
    # identical logits, distinct fold_in data -> not one collapsed draw
    assert len(set(out.tolist())) > 1
    # same fold_in data in a different slot -> identical draw
    perm = jnp.asarray([3, 0, 6, 1, 7, 2, 5, 4])
    out_p = np.asarray(sample_per_slot(
        keys[perm], logits, **kw))
    assert np.array_equal(out_p, out[np.asarray(perm)])
    # a REUSED key replays its draw exactly (why streams must be disjoint)
    twice = jnp.concatenate([keys[:1], keys[:1]], axis=0)
    out_r = np.asarray(sample_per_slot(
        twice, logits[:2], top_k=kw["top_k"][:2], top_p=kw["top_p"][:2],
        temperature=kw["temperature"][:2]))
    assert out_r[0] == out_r[1]


def test_filtered_logits_per_slot_is_the_sampler_distribution():
    """softmax(filtered_logits_per_slot(...)) IS the categorical the
    sampler draws from: drawing from the returned logits with the same
    keys reproduces sample_per_slot exactly.  The speculative rejection
    sampler's p and q hang on this equivalence."""
    from megatron_llm_tpu.generation.sampling import filtered_logits_per_slot

    rng = np.random.default_rng(6)
    logits = jnp.asarray(rng.normal(size=(5, 40)) * 3, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(5))
    top_k = jnp.asarray([1, 4, 0, 0, 2], jnp.int32)
    top_p = jnp.asarray([0.0, 0.0, 0.8, 0.0, 0.0], jnp.float32)
    temp = jnp.asarray([1.0, 0.7, 1.3, 2.0, 1.0], jnp.float32)
    filtered, greedy = filtered_logits_per_slot(
        logits, top_k=top_k, top_p=top_p, temperature=temp, vocab_size=38)
    manual = jnp.where(
        top_k == 1, greedy,
        jax.vmap(lambda k, r: jax.random.categorical(k, r))(keys, filtered))
    out = sample_per_slot(keys, logits, top_k=top_k, top_p=top_p,
                          temperature=temp, vocab_size=38)
    assert np.array_equal(np.asarray(manual), np.asarray(out))


# ---------------------------------------------------------------------------
# cached_jit regression (satellite: id(cfg) keying)
# ---------------------------------------------------------------------------


def test_cached_jit_keys_on_config_content():
    """Two configs with EQUAL contents share one compiled entry (no id
    dependence — the id-recycling hazard of the old key); different contents
    get different entries."""
    clear_jit_cache()
    def mk(hidden_size=32):
        return make_config(
            "llama2", num_layers=1, hidden_size=hidden_size,
            num_attention_heads=2, num_attention_heads_kv=2,
            ffn_hidden_size=64, seq_length=64,
            max_position_embeddings=64, vocab_size=VOCAB)
    cfg_a, cfg_b = mk(), mk()
    assert cfg_a is not cfg_b
    assert config_fingerprint(cfg_a) == config_fingerprint(cfg_b)

    calls = []
    fn_a = cached_jit(cfg_a, "t", (1,), lambda: calls.append(1) or (lambda x: x))
    fn_b = cached_jit(cfg_b, "t", (1,), lambda: calls.append(1) or (lambda x: x))
    assert fn_a is fn_b and len(calls) == 1, "equal configs must share"

    cfg_c = mk(hidden_size=64)
    assert config_fingerprint(cfg_c) != config_fingerprint(cfg_a)
    fn_c = cached_jit(cfg_c, "t", (1,), lambda: calls.append(1) or (lambda x: x))
    assert fn_c is not fn_a and len(calls) == 2

    # GC'd configs cannot alias: the key survives the object, by value
    key_count = len(_JIT_CACHE)
    del cfg_a, cfg_b
    import gc

    gc.collect()
    cfg_d = mk()
    fn_d = cached_jit(cfg_d, "t", (1,), lambda: calls.append(1) or (lambda x: x))
    assert fn_d is fn_b and len(calls) == 2 and len(_JIT_CACHE) == key_count
    clear_jit_cache()
