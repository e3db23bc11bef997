"""Quantized paged KV cache + quantized DP collectives (ISSUE 13).

Contracts pinned here:

* **bf16 untouched**: ``kv_dtype="bf16"`` engines produce plain arrays and
  the same compiled-program keys shape as before (the existing parity
  suites — test_paged_engine / test_prefix_cache / test_speculative /
  test_ragged_tick — are the real bitwise gate; this file covers the new
  modes).
* **analytic error bounds** (ops/kv_quant.py module docstring): one-shot
  page quantization ``|x - q*s| <= s/2``; decode appends that grow the
  page scale re-round once more, ``<= s_final`` (2x the one-shot bound).
* **collision-safe writes**: consecutive rows of one chunk / verify block
  share a page; every token must survive the page-granular update.
* **accuracy gates** (documented in docs/guide/quantization.md): greedy
  tokens match bf16 on the short-horizon sanity workload; per-token
  log-prob deltas stay under ``LOGPROB_GATE`` on the long horizon — across
  prefix-cache on/off, speculative on/off, preempt/resume, and tp=4.
* **compiled-program fingerprints**: an int8 engine must never reuse a
  bf16 executable — the kv mode + scale dtype are part of every cache key.
* **quantized DP all-reduce** (parallel/quantized.py): elementwise error
  within the chunk-scale bound, exact for small leaves, and a loss-delta
  gate vs the bf16-sync baseline (``QDP_LOSS_GATE``) on the CPU-sanity
  pretrain shape at dp=2 — flag off by default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.generation.engine import ContinuousBatchingEngine
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.ops import kv_quant

from tests.parity import dense_greedy

# accuracy gates, measured on the CPU-sanity shapes below and documented
# in docs/guide/quantization.md ("Accuracy gates"): greedy agreement is
# asserted exactly on the short horizon; log-prob deltas on the long
# horizon measured ~3e-4 (int8) — gated at 10x margin
LOGPROB_GATE = 5e-3
# dp=2 quantized-vs-bf16 sync loss delta measured ~1.5e-4 over 8 steps —
# gated at >10x margin
QDP_LOSS_GATE = 2e-3

GREEDY = dict(top_k=1, termination_id=0, use_eod_for_termination=False)

CFG_KW = dict(hidden_size=64, num_attention_heads=4,
              num_attention_heads_kv=4, ffn_hidden_size=128, vocab_size=512,
              seq_length=256, max_position_embeddings=256,
              params_dtype="float32", micro_batch_size=1,
              global_batch_size=1, train_iters=1)


@pytest.fixture(scope="module")
def models():
    from megatron_llm_tpu.generation import DraftModel
    from megatron_llm_tpu.generation.speculative import (
        extend_params_identity,
    )

    cfg = make_config("llama2", num_layers=2, **CFG_KW)
    dcfg = make_config("llama2", num_layers=1, **CFG_KW)
    dparams = init_model_params(dcfg, jax.random.PRNGKey(1))
    params = extend_params_identity(dcfg, dparams, cfg, jax.random.PRNGKey(0))
    return {"cfg": cfg, "params": params,
            "draft": DraftModel(dcfg, dparams)}


def _prompts(n, length, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, length)]
            for _ in range(n)]


def _engine(models, kv_dtype, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(models["cfg"], models["params"],
                                    kv_dtype=kv_dtype, **kw)


def _decode(eng, prompts, gen_len=12, **kw):
    reqs = [eng.submit(p, gen_len, **{**GREEDY, **kw}) for p in prompts]
    eng.run_until_idle()
    return [r.result(timeout=120) for r in reqs]


# ---------------------------------------------------------------------------
# ops/kv_quant.py unit contracts
# ---------------------------------------------------------------------------


def test_one_shot_page_quant_error_bound():
    """Whole-page quantization error <= scale/2 per element — the
    int8_quant_error_bound-style analytic bound, both storage dtypes."""
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(0, 3.0, (5, 16, 4, 8)).astype(np.float32))
    for kv_dtype in ("int8", "fp8"):
        qp = kv_quant.quantize_pages(vals, kv_dtype)
        back = kv_quant.dequantize_pages(qp, jnp.float32)
        err = np.abs(np.asarray(back) - np.asarray(vals))
        # per-(page, head) bound: scale/2
        bound = np.asarray(qp.scale)[:, None, :, None] / 2.0
        if kv_dtype == "fp8":
            # fp8 rounding is relative (RNE at ~2^-3 of magnitude), not
            # the uniform int8 grid — bound by the format's worst-case
            # relative step instead
            bound = np.maximum(bound, np.abs(np.asarray(vals)) * 2 ** -3)
        assert (err <= bound + 1e-7).all(), kv_dtype
        assert float(jnp.max(jnp.abs(back))) <= float(
            jnp.max(jnp.abs(vals))) * 1.01


def test_append_requant_error_bound():
    """Token-by-token appends with growing magnitudes: each earlier token
    is re-rounded every time the page scale GROWS, adding <= s_new/2 per
    growth — the documented per-page append bound is the running sum
    ``s_at_write/2 + sum(s_g/2 over later growths)`` (ops/kv_quant.py
    module docstring), tracked here against the actual scale history."""
    rng = np.random.default_rng(1)
    page, nkv, d = 16, 4, 8
    pool = kv_quant.make_pool((2, page, nkv, d), "int8", jnp.float32)
    # magnitudes ramp 1x -> 4x so the page scale grows on most appends
    toks = [rng.normal(0, 1.0 + 3.0 * i / (page - 1), (nkv, d))
            .astype(np.float32) for i in range(page)]
    bounds = np.zeros((page, nkv), np.float64)
    prev_scale = np.zeros((nkv,), np.float64)
    for off, t in enumerate(toks):
        pool = kv_quant.paged_write(
            pool, jnp.asarray([[1]], jnp.int32), jnp.asarray([[off]]),
            jnp.asarray(t)[None, None])
        s = np.asarray(pool.scale[1], np.float64)
        bounds[off] = s / 2.0  # this token's own rounding
        grew = s > prev_scale + 1e-12
        # every EARLIER token re-rounds under the grown scale
        bounds[:off][:, grew] += s[grew] / 2.0
        prev_scale = s
    back = np.asarray(kv_quant.dequantize_pages(
        kv_quant.QuantPagedKV(pool.q[1], pool.scale[1]), jnp.float32))
    vals = np.stack(toks)
    err = np.abs(back - vals)
    assert (err <= bounds[:, :, None] + 1e-7).all()
    # and in PRACTICE the random-walk accumulation stays near the
    # one-shot figure: well under 2x s_final (the rule-of-thumb
    # docs/guide/quantization.md quotes)
    s_final = np.asarray(pool.scale[1])
    assert float(err.max()) < 2.0 * float(s_final.max())


def test_collision_safe_chunk_write():
    """A whole chunk's rows target the same pages (the ragged/prefill
    shape): every token must survive the collision-safe 3-phase update,
    within the one-shot bound (all rows fresh-quantize together)."""
    rng = np.random.default_rng(2)
    page, nkv, d = 16, 4, 8
    pool = kv_quant.make_pool((4, page, nkv, d), "int8", jnp.float32)
    # 32 rows = pages 1..2 fully written in ONE call, offs 0..15 each
    vals = rng.normal(0, 2.0, (1, 32, nkv, d)).astype(np.float32)
    page_ids = np.repeat([1, 2], 16)[None]
    offs = np.tile(np.arange(16), 2)[None]
    out = kv_quant.paged_write(pool, jnp.asarray(page_ids),
                               jnp.asarray(offs), jnp.asarray(vals))
    for pid, lo in ((1, 0), (2, 16)):
        back = np.asarray(kv_quant.dequantize_pages(
            kv_quant.QuantPagedKV(out.q[pid], out.scale[pid]), jnp.float32))
        want = vals[0, lo:lo + 16]
        bound = np.asarray(out.scale[pid])[None, :, None] / 2.0
        assert (np.abs(back - want) <= bound + 1e-7).all()


def test_fresh_page_resets_stale_scale():
    """A freed page's stale (huge) scale must not poison the next tenant:
    an ``offs == 0`` write resets the page scale to the new content."""
    page, nkv, d = 16, 4, 8
    pool = kv_quant.make_pool((3, page, nkv, d), "int8", jnp.float32)
    big = jnp.full((1, 1, nkv, d), 1000.0)
    pool = kv_quant.paged_write(pool, jnp.asarray([[2]]),
                                jnp.asarray([[0]]), big)
    assert float(pool.scale[2].max()) > 1.0
    small = jnp.full((1, 1, nkv, d), 0.5)
    pool = kv_quant.paged_write(pool, jnp.asarray([[2]]),
                                jnp.asarray([[0]]), small)
    # scale reset: 0.5/127, not inherited from the 1000.0 tenant
    assert float(pool.scale[2].max()) < 0.01
    back = kv_quant.dequantize_pages(
        kv_quant.QuantPagedKV(pool.q[2], pool.scale[2]), jnp.float32)
    assert abs(float(back[0, 0, 0]) - 0.5) < 0.01


def test_mid_page_append_preserves_prefix():
    """An ``offs > 0`` append keeps earlier tokens in the page (requant
    merge), unlike the fresh-reset path."""
    page, nkv, d = 16, 4, 8
    pool = kv_quant.make_pool((3, page, nkv, d), "int8", jnp.float32)
    first = jnp.full((1, 1, nkv, d), 2.0)
    pool = kv_quant.paged_write(pool, jnp.asarray([[1]]),
                                jnp.asarray([[0]]), first)
    second = jnp.full((1, 1, nkv, d), 4.0)
    pool = kv_quant.paged_write(pool, jnp.asarray([[1]]),
                                jnp.asarray([[1]]), second)
    back = np.asarray(kv_quant.dequantize_pages(
        kv_quant.QuantPagedKV(pool.q[1], pool.scale[1]), jnp.float32))
    s = float(pool.scale[1].max())
    assert abs(back[0, 0, 0] - 2.0) <= s  # re-rounded once: 2x bound
    assert abs(back[1, 0, 0] - 4.0) <= s / 2 + 1e-7


def test_bf16_pool_is_plain_array():
    """The default mode never builds a container — the bitwise contract's
    structural half (the parity suites are the behavioral half)."""
    pool = kv_quant.make_pool((2, 4, 16, 4, 8), "bf16", jnp.float32)
    assert not kv_quant.is_quantized(pool)
    assert kv_quant.scale_nbytes(pool) == 0
    q = kv_quant.make_pool((2, 4, 16, 4, 8), "int8", jnp.float32)
    assert kv_quant.is_quantized(q)
    assert q.q.dtype == jnp.int8 and q.scale.shape == (2, 4, 4)
    # int8 value storage is 1/4 the fp32 pool bytes (1/2 of bf16)
    assert kv_quant.pool_nbytes(q) * 4 == kv_quant.pool_nbytes(pool)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("nkv,d", [(1, 64), (8, 64), (8, 128)],
                         ids=["mqa1x64", "gqa8x64", "gqa8x128"])
def test_row_round_trip_through_the_owner(nkv, d, kv_dtype):
    """ops/kv_quant.py owns the pool's physical row: a write of logical
    ``(page, offset, head, d)`` keys and values into one layer of the
    layered pool reads back through every logical reader (the gather, the
    handoff's wire leaves and their inverse) — exactly for a plain pool,
    within the page bound for a quantized one — in whole 128-lane rows,
    with the other layer untouched."""
    from megatron_llm_tpu.ops.paged_attention import paged_gather_kv

    rng = np.random.default_rng(nkv * d)
    layers, pages, page = 2, 6, 8
    pool = kv_quant.make_kv_pool(layers, pages, page, nkv, d, kv_dtype,
                                 jnp.float32)
    assert kv_quant.row_width(pool) == 2 * nkv * d
    assert kv_quant.row_width(pool) % 128 == 0
    assert kv_quant.page_size_of(pool) == page
    assert kv_quant.values_of(pool).shape == (layers, pages, page,
                                              2 * nkv * d)
    # two whole pages (3 and 5) and half of page 1, one call
    n_tok = 2 * page + page // 2
    page_ids = np.concatenate([np.full(page, 3), np.full(page, 5),
                               np.full(page // 2, 1)])[None]
    offs = np.concatenate([np.arange(page), np.arange(page),
                           np.arange(page // 2)])[None]
    k = rng.normal(0, 2.0, (1, n_tok, nkv, d)).astype(np.float32)
    v = rng.normal(0, 2.0, (1, n_tok, nkv, d)).astype(np.float32)
    layer = jnp.int32(1)
    pool = jax.jit(kv_quant.paged_write)(
        pool, jnp.asarray(page_ids), jnp.asarray(offs),
        kv_quant.pack_kv(jnp.asarray(k), jnp.asarray(v)), layer)

    tables = jnp.asarray([[3, 5, 1]])
    got_k, got_v = paged_gather_kv(pool, tables, d, jnp.float32, layer)
    assert got_k.shape == got_v.shape == (1, 3 * page, nkv, d)
    tol = 0.0 if kv_dtype == "bf16" else max(
        kv_quant.kv_error_bound(jnp.asarray(x), kv_dtype) for x in (k, v))
    if kv_dtype == "fp8":   # relative rounding: the format's worst step
        tol = max(tol, float(np.abs(np.concatenate([k, v])).max()) * 2 ** -3)
    np.testing.assert_allclose(np.asarray(got_k)[0, :n_tok], k[0], atol=tol)
    np.testing.assert_allclose(np.asarray(got_v)[0, :n_tok], v[0], atol=tol)
    # layer 0 was not touched
    z_k, z_v = paged_gather_kv(pool, tables, d, jnp.float32, jnp.int32(0))
    assert not np.asarray(z_k).any() and not np.asarray(z_v).any()

    # the wire's logical leaves and back: the stored bytes verbatim
    ids = np.asarray([3, 5, 1])
    host = jax.device_get(jax.tree.map(lambda a: a[:, ids], pool))
    leaves = kv_quant.kv_to_leaves(host, d)
    quant = kv_dtype != "bf16"
    sfx = ".q" if quant else ""
    assert leaves["k" + sfx].shape == (layers, 3, page, nkv, d)
    assert leaves["v" + sfx].shape == (layers, 3, page, nkv, d)
    if quant:
        assert leaves["k.scale"].shape == (layers, 3, nkv)
        logical_k = (leaves["k.q"].astype(np.float32)
                     * leaves["k.scale"][..., None, :, None])
    else:
        logical_k = leaves["k"]
    np.testing.assert_allclose(
        logical_k[1].reshape(3 * page, nkv, d)[:n_tok], k[0], atol=tol)
    back = kv_quant.kv_from_leaves(leaves, quant)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine accuracy gates
# ---------------------------------------------------------------------------


def test_greedy_agreement_short_horizon(models):
    """int8 AND fp8 greedy tokens match bf16 exactly on the sanity
    workload (short horizon, cache on)."""
    prompts = _prompts(3, 37)
    base = _decode(_engine(models, "bf16"), prompts)
    for kv_dtype in ("int8", "fp8"):
        got = _decode(_engine(models, kv_dtype), prompts)
        for (tb, _), (tq, _) in zip(base, got):
            assert tb == tq, kv_dtype


def test_logprob_delta_long_horizon(models):
    """Per-token log-prob delta vs bf16 stays under LOGPROB_GATE over a
    long decode (the documented int8 accuracy gate)."""
    prompts = _prompts(2, 33, seed=3)
    base = _decode(_engine(models, "bf16"), prompts, gen_len=64)
    got = _decode(_engine(models, "int8"), prompts, gen_len=64)
    for (tb, lb), (tq, lq) in zip(base, got):
        assert tb == tq
        delta = max(abs(a - b) for a, b in zip(lb, lq))
        assert delta < LOGPROB_GATE, delta


def test_cache_on_off_agreement_int8(models):
    """Prefix-cache hits replay quantized pages + scales: warm-cache
    decode tokens and log-probs equal the cold decode (deterministic
    quantization makes this exact at int8 too)."""
    shared = _prompts(1, 48, seed=4)[0]
    tails = _prompts(2, 6, seed=5)
    warm = _engine(models, "int8")
    _decode(warm, [shared + tails[0]], gen_len=8)
    h0 = warm.prefix_hit_tokens
    warm_out = _decode(warm, [shared + tails[1]], gen_len=8)
    assert warm.prefix_hit_tokens - h0 >= 48 // warm.page_size * \
        warm.page_size  # pages actually reused
    cold = _engine(models, "int8")
    cold_out = _decode(cold, [shared + tails[1]], gen_len=8)
    assert warm_out[0][0] == cold_out[0][0]
    assert warm_out[0][1] == cold_out[0][1]
    nocache = _engine(models, "int8", prefix_cache=False)
    nc_out = _decode(nocache, [shared + tails[1]], gen_len=8)
    assert nc_out[0][0] == cold_out[0][0]


def test_speculative_agreement_int8(models):
    """Speculation at int8: spec-on tokens equal spec-off tokens on the
    sanity workload, and the identity-extended draft still accepts
    everything (both models read the same quantized page discipline)."""
    prompts = _prompts(3, 37)
    plain = _decode(_engine(models, "int8"), prompts)
    eng = _engine(models, "int8", spec_k=2, spec_draft=models["draft"])
    spec = _decode(eng, prompts)
    for (tp_, _), (ts, _) in zip(plain, spec):
        assert tp_ == ts
    assert eng.spec_draft_tokens > 0
    assert eng.spec_accepted_tokens == eng.spec_draft_tokens


def test_preempt_resume_agreement_int8(models):
    """Preemption parks quantized pages (values + scales) in the trie;
    resume matches them back and continues — tokens equal the
    uninterrupted run."""
    prompt = _prompts(1, 37)[0]
    eng = _engine(models, "int8", max_slots=2)
    req = eng.submit(prompt, 16, **GREEDY)
    for _ in range(8):
        eng.step()
    assert eng.preempt(req)
    eng.run_until_idle()
    got = req.result(timeout=120)
    want = _decode(_engine(models, "int8", max_slots=2), [prompt],
                   gen_len=16)[0]
    assert got[0] == want[0]


def test_tp4_agreement_int8(models):
    """tp=4 int8 engine: pool + scales shard over the heads dim; tokens
    equal the single-chip int8 engine."""
    prompts = _prompts(2, 37)
    single = _decode(_engine(models, "int8", max_slots=2), prompts,
                     gen_len=10)
    mesh = build_mesh(tensor_model_parallel_size=4,
                      devices=jax.devices()[:4])
    with global_mesh(mesh):
        eng = _engine(models, "int8", max_slots=2, mesh=mesh)
        assert eng.pool.kv.q.sharding.spec[3] == "tp"
        assert eng.pool.kv.scale.sharding.spec[2] == "tp"
        sharded = _decode(eng, prompts, gen_len=10)
    for (ts, _), (tm, _) in zip(single, sharded):
        assert ts == tm


def test_int8_ragged_tick_matches_dense_greedy(models):
    """int8 pages under the ragged tick — a multi-chunk prompt prefilling
    beside decoding rows — still give the greedy tokens of the dense
    single-stream path, which has no pages to quantize; log-probs stay
    inside the documented int8 gate."""
    prompts = _prompts(2, 37) + _prompts(1, 100, seed=5)
    eng = _engine(models, "int8")
    early = [eng.submit(p, 12, **GREEDY) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    late = eng.submit(prompts[2], 12, **GREEDY)
    mixed = False
    while not late.finished:
        mixed |= late._phase == "prefill" and any(
            r._phase == "decode" for r in early)
        eng.step()
    eng.run_until_idle()
    assert mixed, "no tick carried prompt rows beside decode rows"
    for p, req in zip(prompts, early + [late]):
        toks, lps = req.result(timeout=120)
        ref_toks, ref_lp = dense_greedy(models["cfg"], models["params"],
                                        p, 12)
        assert toks == ref_toks
        delta = np.max(np.abs(np.asarray(lps) - ref_lp[len(p) - 1:]))
        assert delta < LOGPROB_GATE, delta


# ---------------------------------------------------------------------------
# compiled-program fingerprints + telemetry
# ---------------------------------------------------------------------------


def test_kv_dtype_flips_compiled_program_keys(models):
    """Flipping --kv_dtype must produce DISTINCT cached_jit keys for the
    tick (an int8 engine reusing a bf16 executable would read int8 bytes
    as bf16) — the kv mode + storage/scale dtypes live in every key."""
    e16 = _engine(models, "bf16")
    e8 = _engine(models, "int8")
    assert e16.pool.kv_statics != e8.pool.kv_statics
    assert "int8" in str(e8.pool.kv_statics)
    assert e8.pool.kv_statics[-1] == "float32"  # scale dtype folded in
    before = set(gen._JIT_CACHE)
    f16 = e16._ragged_tick(0)
    f8 = e8._ragged_tick(0)
    assert f16 is not f8
    new_keys = [k for k in gen._JIT_CACHE if k not in before]
    tick_keys = [k for k in set(gen._JIT_CACHE)
                 if k[1] == "engine_ragged_tick"]
    kv_entries = {k: [t for t in k[2] if isinstance(t, tuple)
                      and t and t[0] == "kv"] for k in tick_keys}
    assert all(v for v in kv_entries.values()), (
        "every tick key must carry the kv statics tuple")
    del new_keys


def test_kv_metrics_and_health(models):
    """/metrics gains mlt_engine_kv_pool_bytes / kv_scale_bytes /
    kv_dtype info; /health carries kv_dtype + byte budget; the router's
    ReplicaView parses them (capacity-aware routing input)."""
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.observability import registry as obs_registry
    from megatron_llm_tpu.serving.router.registry import ReplicaView

    eng = _engine(models, "int8")
    srv = MegatronServer(eng)
    health = srv.health()
    assert health["kv_dtype"] == "int8"
    assert health["kv_pool_bytes"] == eng.pool.kv_pool_bytes() > 0
    assert health["kv_scale_bytes"] == eng.pool.kv_scale_bytes() > 0
    text = srv.metrics_text()
    assert "mlt_engine_kv_pool_bytes" in text
    assert "mlt_engine_kv_scale_bytes" in text
    assert 'mlt_engine_kv_dtype_info{kv_dtype="int8"}' in text
    view = ReplicaView.parse("http://x", health)
    assert view.kv_dtype == "int8"
    assert view.kv_pool_bytes == eng.pool.kv_pool_bytes()
    assert view.free_kv_bytes is not None and view.free_kv_bytes > 0
    # pre-ISSUE-13 replicas keep conservative defaults
    old = ReplicaView.parse("http://y", {"status": "ok"})
    assert old.kv_dtype == "bf16" and old.free_kv_bytes is None
    del obs_registry


def test_int8_pool_bytes_half_of_bf16():
    """The capacity lever itself: at equal page counts an int8 pool's
    value bytes are half a bf16 pool's (quarter of this fp32-on-CPU
    suite's), so a fixed byte budget carries ~2x the pages (modulo the
    reported scale overhead)."""
    cfg = make_config("llama2", num_layers=2, **{**CFG_KW,
                                                 "params_dtype": "bfloat16"})
    from megatron_llm_tpu.generation.pools import PagedKVPool

    p16 = PagedKVPool(cfg, 33, 16)
    p8 = PagedKVPool(cfg, 33, 16, kv_dtype="int8")
    assert p8.kv_pool_bytes() * 2 == p16.kv_pool_bytes()
    assert p16.kv_scale_bytes() == 0
    # scale overhead: one f32 per (layer, page, head) per cache — small
    # relative to page payload (page_size * d elements)
    assert p8.kv_scale_bytes() < p8.kv_pool_bytes() / 16


def test_lock_rule_covers_peak_active_slots():
    """Anti-vacuity (the ISSUE 10 idiom): the new capacity-telemetry
    field really is in the graftcheck lock model for the engine — the
    repo sweep's cleanliness over engine.py covers it, not vacuously."""
    import ast as ast_mod
    import os

    from tools.graftcheck import core
    from tools.graftcheck.rules.locks import LockDisciplineRule

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "megatron_llm_tpu", "generation",
                        "engine.py")
    ctx = core.FileContext(path)
    rule = LockDisciplineRule()
    for node in ast_mod.walk(ctx.tree):
        if isinstance(node, ast_mod.ClassDef) \
                and node.name == "ContinuousBatchingEngine":
            model = rule._build(ctx, node)
            assert model is not None
            assert "peak_active_slots" in model.guards
            assert model.guards["peak_active_slots"] == {"_lock"}
            break
    else:
        raise AssertionError("engine class not found")


def test_peak_active_slots_on_health(models):
    """The capacity bench's headline number is first-class telemetry:
    /health carries the engine's concurrent-decode high-water mark."""
    from megatron_llm_tpu.generation.server import MegatronServer

    eng = _engine(models, "int8")
    _decode(eng, _prompts(3, 37), gen_len=6)
    assert eng.peak_active_slots >= 3
    assert MegatronServer(eng).health()["peak_active_slots"] == \
        eng.peak_active_slots


def test_kv_dtype_flag_flows_from_config(models):
    """cfg.inference.kv_dtype drives the engine default (the --kv_dtype
    flag path), and bad values fail loudly."""
    import dataclasses

    cfg = dataclasses.replace(models["cfg"])
    cfg.inference = dataclasses.replace(cfg.inference, kv_dtype="int8")
    eng = ContinuousBatchingEngine(cfg, models["params"], max_slots=2,
                                   max_seq=128)
    assert eng.kv_dtype == "int8"
    assert kv_quant.is_quantized(eng.pool.kv)
    with pytest.raises(AssertionError):
        _engine(models, "int4")


# ---------------------------------------------------------------------------
# quantized DP gradient all-reduce (parallel/quantized.py)
# ---------------------------------------------------------------------------


def _qdp_mesh(n=2):
    return build_mesh(data_parallel_size=n, devices=jax.devices()[:n])


def test_quantized_allreduce_unit_bound():
    """Elementwise: quantized dp-mean within the per-chunk scale bound of
    the exact mean; small leaves exact (pmean path)."""
    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu.parallel import compat
    from megatron_llm_tpu.parallel.quantized import (
        quantized_allreduce_mean,
    )

    mesh = _qdp_mesh(4)
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1.0, (4, 8192)).astype(np.float32)
    small = rng.normal(0, 1.0, (4, 64)).astype(np.float32)

    def body(xl, sl):
        return (quantized_allreduce_mean(xl[0], "dp", 4),
                quantized_allreduce_mean(sl[0], "dp", 4))

    f = jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P(), P()), axis_names=set(mesh.axis_names),
        check_vma=False))
    got, got_small = f(jnp.asarray(x), jnp.asarray(small))
    ref = x.mean(0)
    # bound: one sender-side + one result-side rounding per element
    s_in = np.abs(x).reshape(4, 4, -1).max(axis=2) / 127.0
    bound = s_in.max() / 2.0 + np.abs(ref).max() / 127.0 / 2.0 + 1e-6
    assert np.max(np.abs(np.asarray(got) - ref)) <= bound * 2
    # small leaves: exact pmean
    np.testing.assert_allclose(np.asarray(got_small), small.mean(0),
                               rtol=1e-6)


def _pretrain_losses(quantized: bool, steps: int = 8):
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=4, ffn_hidden_size=128, vocab_size=512,
        seq_length=64, max_position_embeddings=64, params_dtype="float32",
        micro_batch_size=2, global_batch_size=8, train_iters=steps,
        lr=1e-3, quantized_grad_allreduce=quantized)
    cfg.parallel.data_parallel_size = 2
    from megatron_llm_tpu.training_step import make_jitted_train_step

    mesh = _qdp_mesh(2)
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        step, _, sh = make_jitted_train_step(cfg, mesh, params)
        opt_state = sh["opt_state_value"]
        rng = np.random.default_rng(0)
        losses = []
        for it in range(steps):
            tokens = rng.integers(1, 512, (8, 64)).astype(np.int32)
            batch = sh["place_batch"](
                {"tokens": tokens, "labels": tokens,
                 "loss_mask": np.ones((8, 64), np.float32)})
            params, opt_state, mets = step(params, opt_state, batch,
                                           jnp.int32(it))
            losses.append(float(mets["lm loss"]))
    return losses


def test_quantized_dp_loss_trajectory_gate():
    """THE acceptance gate: the CPU-sanity pretrain loss trajectory under
    --quantized_grad_allreduce stays within QDP_LOSS_GATE (relative) of
    the bf16-sync baseline at dp=2, microbatch accumulation included
    (gbs 8 = mbs 2 x dp 2 x num_micro 2)."""
    base = _pretrain_losses(False)
    quant = _pretrain_losses(True)
    # step-0 forward differs only by reduction order (dp-mean of local
    # means vs one global mean) — float-noise, not quantization
    assert abs(base[0] - quant[0]) < 1e-5
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(base, quant))
    assert rel < QDP_LOSS_GATE, (rel, base, quant)
    # and both actually trained
    assert base[-1] < base[0] and quant[-1] < quant[0]


def test_quantized_dp_off_by_default_and_scoped():
    """Flag default False; unsupported meshes are refused loudly."""
    from megatron_llm_tpu.parallel.quantized import (
        make_quantized_dp_grad_fn,
        quantized_dp_supported,
    )

    cfg = make_config("llama2", num_layers=2, **CFG_KW)
    assert cfg.training.quantized_grad_allreduce is False
    assert not quantized_dp_supported(cfg, None)
    mesh1 = build_mesh(devices=jax.devices()[:1])
    assert not quantized_dp_supported(cfg, mesh1)
    mesh_tp = build_mesh(tensor_model_parallel_size=2,
                         data_parallel_size=2, devices=jax.devices()[:4])
    assert not quantized_dp_supported(cfg, mesh_tp)
    with pytest.raises(AssertionError):
        make_quantized_dp_grad_fn(cfg, mesh_tp, None, 1)
