"""Speculative decoding tests (ISSUE 9).

Gates: (1) greedy speculative decode is BITWISE identical (tokens and
log-probs, jnp fallback) to ``spec_k=0`` — for any draft, cache on/off,
across speculation depths, through stop-token truncation and through
preemption/resume; (2) sampled speculative decode matches the target
model's distribution: the acceptance rule passes a direct statistical
test against the theoretical emission law, and engine-level marginals
match non-speculative sampling; (3) the draft shares the page pool
correctly — one page id addresses both caches, refcounts drain whole,
admission accounting is unchanged; (4) per-slot adaptive depth shrinks
on low acceptance; (5) the telemetry surface (``mlt_engine_spec_*``,
``spec_stats``, ``/health``) is live.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from megatron_llm_tpu.generation import (
    ContinuousBatchingEngine,
    DraftModel,
)
from megatron_llm_tpu.generation.speculative import (
    check_draft_compat,
    extend_params_identity,
    speculative_acceptance,
)
from megatron_llm_tpu.generation.speculative.draft import parse_draft_spec

from tests.parity import assert_same_generations

VOCAB = 67


@pytest.fixture(scope="module")
def models():
    """Target (2L), an independent random draft (1L, smaller), and an
    identity-extended target that provably agrees with a same-width
    draft."""
    from megatron_llm_tpu.models import init_model_params, make_config

    def mk(layers, hidden, heads, nkv, ffn):
        return make_config(
            "llama2", num_layers=layers, hidden_size=hidden,
            num_attention_heads=heads, num_attention_heads_kv=nkv,
            ffn_hidden_size=ffn, seq_length=128,
            max_position_embeddings=256, vocab_size=VOCAB,
            hidden_dropout=0.0, attention_dropout=0.0,
            params_dtype="float32", use_flash_attn=False,
        )

    cfg = mk(2, 64, 4, 2, 128)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    dcfg = mk(1, 32, 2, 2, 64)
    dparams = init_model_params(dcfg, jax.random.PRNGKey(1))
    # same-width 1-layer draft + target whose extra layer is an exact
    # identity: greedy acceptance is provably 100%
    acfg = mk(1, 64, 4, 2, 128)
    aparams = init_model_params(acfg, jax.random.PRNGKey(2))
    agree_params = extend_params_identity(acfg, aparams, cfg,
                                          jax.random.PRNGKey(3))
    return {
        "cfg": cfg, "params": params,
        "draft": DraftModel(dcfg, dparams),
        "agree_draft": DraftModel(acfg, aparams),
        "agree_params": agree_params,
    }


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(cfg, params, None, **kw)


def _run(eng, jobs):
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.run_until_idle()
    out = []
    for r in reqs:
        toks, lps = r.result(timeout=60)
        out.append((toks, lps))
    return out


def _greedy_jobs(n_new=18):
    shared = [2 + (i * 7) % 60 for i in range(48)]  # 3 full pages @ 16
    jobs = []
    for i in range(4):
        tail = [3 + (i * 11 + j) % 60 for j in range(3 + 9 * i)]
        jobs.append((shared + tail, n_new,
                     dict(top_k=1, termination_id=10 ** 9)))
    jobs.append(([5, 9, 2], n_new, dict(top_k=1, termination_id=10 ** 9)))
    # page-aligned full duplicates: the second takes the COW path
    jobs.append((list(shared), 10, dict(top_k=1, termination_id=10 ** 9)))
    jobs.append((list(shared), 10, dict(top_k=1, termination_id=10 ** 9)))
    return jobs


# ---------------------------------------------------------------------------
# Losslessness (greedy): the same tokens, log-probs to a few fp32 ulps
# (tests/parity.py says why not bit for bit)
# ---------------------------------------------------------------------------


def test_greedy_spec_bitwise_vs_nonspec(models):
    """spec_k in {1, 3} with a draft the target almost never agrees with:
    the emitted stream must still be the greedy target stream — tokens
    AND log-probs — including prefix-cache hits and COW."""
    cfg, params = models["cfg"], models["params"]
    jobs = _greedy_jobs()
    base = _engine(cfg, params, spec_k=0)
    res0 = []
    for j in jobs:  # submit one-by-one so later jobs hit the cache
        res0.extend(_run(base, [j]))
    for k in (1, 3):
        eng = _engine(cfg, params, spec_k=k, spec_draft=models["draft"])
        res = []
        for j in jobs:
            res.extend(_run(eng, [j]))
        assert_same_generations(res0, res, f"spec_k={k}")
        assert eng.spec_ticks > 0
        assert eng.cow_copies >= 1  # page-aligned duplicate took COW


def test_greedy_spec_bitwise_cache_off(models):
    cfg, params = models["cfg"], models["params"]
    jobs = _greedy_jobs()
    res0 = _run(_engine(cfg, params, spec_k=0, prefix_cache=False), jobs)
    res1 = _run(_engine(cfg, params, spec_k=3, prefix_cache=False,
                        spec_draft=models["draft"]), jobs)
    assert_same_generations(res0, res1)


def test_greedy_spec_bitwise_high_acceptance(models):
    """The agreeing draft accepts ~everything — the fast path (multi-token
    blocks, bonus tokens every tick) must be just as lossless."""
    cfg = models["cfg"]
    params = models["agree_params"]
    jobs = _greedy_jobs()
    res0 = _run(_engine(cfg, params, spec_k=0), jobs)
    eng = _engine(cfg, params, spec_k=4, spec_draft=models["agree_draft"])
    res1 = _run(eng, jobs)
    assert_same_generations(res0, res1)
    stats = eng.spec_stats()
    assert stats["acceptance_rate"] == 1.0, stats
    # multi-token progress: far fewer ticks than emitted tokens
    assert eng.spec_emitted_tokens > 2 * eng.spec_ticks


def test_greedy_spec_stop_token_truncation(models):
    """A termination token landing mid-accepted-block must cut generation
    at exactly the position non-speculative decode stops at."""
    cfg, params = models["cfg"], models["params"]
    prompt = [5, 9, 2, 33, 17]
    probe = _run(_engine(cfg, params, spec_k=0),
                 [(prompt, 16, dict(top_k=1, termination_id=10 ** 9))])
    gen0 = probe[0][0][len(prompt):]
    stop = gen0[4]  # force a stop mid-stream (and mid-verify-block)
    jobs = [(prompt, 16, dict(top_k=1, termination_id=stop))]
    res0 = _run(_engine(cfg, params, spec_k=0), jobs)
    res1 = _run(_engine(cfg, params, spec_k=4,
                        spec_draft=models["agree_draft"],
                        spec_adaptive=False), jobs)
    assert_same_generations(res0, res1)
    assert res0[0][0][-1] == stop and len(res0[0][0]) < len(prompt) + 16


def test_greedy_spec_bitwise_under_preemption(models):
    """Preempt a speculating slot mid-decode (pages parked in the trie,
    draft pages released through the same path), resume, and the output
    must still be the non-speculative stream."""
    cfg, params = models["cfg"], models["params"]
    prompt = [2 + (j * 5) % 60 for j in range(40)]
    jobs = [(prompt, 20, dict(top_k=1, termination_id=10 ** 9))]
    res0 = _run(_engine(cfg, params, spec_k=0), jobs)

    eng = _engine(cfg, params, spec_k=3, spec_draft=models["draft"])
    req = eng.submit(*jobs[0][:2], **jobs[0][2])
    while req._phase != "decode" or len(req.generated) < 5:
        eng.step()
    assert eng.preempt(req), "request should be preemptible"
    assert req._phase == "queued" and not req._mem[0].pages
    eng.run_until_idle()
    toks, lps = req.result(timeout=60)
    assert_same_generations([(toks, lps)], res0)
    assert req._preemptions == 1


# ---------------------------------------------------------------------------
# Sampled losslessness (distribution match)
# ---------------------------------------------------------------------------


def test_acceptance_rule_matches_target_distribution():
    """Drive :func:`speculative_acceptance` with synthetic p/q over a tiny
    vocab, many trials: the first emitted token's empirical distribution
    must match p_1, and the draft-acceptance rate must match the
    theoretical sum(min(p, q))."""
    rng = np.random.default_rng(0)
    v, K, n = 8, 3, 20000
    q_dist = rng.dirichlet(np.ones(v), size=K)          # [K, v]
    p_dist = rng.dirichlet(np.ones(v), size=K + 1)      # [K+1, v]

    # draft tokens sampled from q (position j uses q_dist[j])
    draft = np.stack(
        [rng.choice(v, size=n, p=q_dist[j]) for j in range(K)], axis=1)
    u = rng.random((n, K)).astype(np.float32)
    emit_keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    q_filt = jnp.log(jnp.asarray(q_dist, jnp.float32))[None].repeat(n, 0)
    t_filt = jnp.log(jnp.asarray(p_dist, jnp.float32))[None].repeat(n, 0)
    t_greedy = jnp.argmax(t_filt, axis=-1).astype(jnp.int32)
    accepted, counts, emit = jax.jit(speculative_acceptance)(
        jnp.asarray(draft, jnp.int32), q_filt, t_filt, t_greedy,
        jnp.zeros((n,), bool), jnp.full((n,), K, jnp.int32),
        jnp.asarray(u), emit_keys)
    accepted = np.asarray(accepted)
    emit = np.asarray(emit)

    # (a) first-draft acceptance rate == sum(min(p_1, q_1))
    theo = np.minimum(p_dist[0], q_dist[0]).sum()
    emp = float((accepted >= 1).mean())
    assert abs(emp - theo) < 0.02, (emp, theo)

    # (b) the emitted token at position 0 is distributed exactly as p_1
    # (accepted draft OR rejection-residual draw — the speculative
    # sampling theorem)
    first = emit[:, 0]
    emp_dist = np.bincount(first, minlength=v) / n
    tv = 0.5 * np.abs(emp_dist - p_dist[0]).sum()
    assert tv < 0.02, (tv, emp_dist, p_dist[0])

    # (c) k_eff masking: depth-0 rows emit exactly one token from p_1
    accepted0, counts0, emit0 = jax.jit(speculative_acceptance)(
        jnp.asarray(draft, jnp.int32), q_filt, t_filt, t_greedy,
        jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32),
        jnp.asarray(u), emit_keys)
    assert int(np.asarray(accepted0).max()) == 0
    assert np.all(np.asarray(counts0) == 1)
    tv0 = 0.5 * np.abs(
        np.bincount(np.asarray(emit0)[:, 0], minlength=v) / n
        - p_dist[0]).sum()
    assert tv0 < 0.02, tv0


def test_sampled_spec_marginals_match_nonspec(models):
    """Engine-level: the same sampled workload (top_k=5, many seeds)
    through spec and non-spec engines produces matching first-token
    marginals — and both match the target model's actual top-k=5
    distribution."""
    cfg, params = models["cfg"], models["params"]
    prompt = [7, 3, 29, 11]
    n, k_new = 320, 3

    def first_tokens(spec_k):
        kw = {} if not spec_k else dict(
            spec_k=spec_k, spec_draft=models["draft"])
        eng = _engine(cfg, params, max_slots=8, max_queue=0, **kw)
        reqs = [eng.submit(prompt, k_new, top_k=5, temperature=1.0,
                           seed=i, termination_id=10 ** 9)
                for i in range(n)]
        eng.run_until_idle()
        for r in reqs:
            r.result(timeout=120)
        return np.asarray([r.generated[0] for r in reqs])

    t0 = first_tokens(0)
    t1 = first_tokens(3)
    # same support (top-5 of the same logits row)
    assert set(t1) <= set(np.unique(t0)) | set(np.unique(t1))
    d0 = np.bincount(t0, minlength=VOCAB) / n
    d1 = np.bincount(t1, minlength=VOCAB) / n
    tv = 0.5 * np.abs(d0 - d1).sum()
    assert tv < 0.15, (tv, np.nonzero(d0)[0], np.nonzero(d1)[0])


# ---------------------------------------------------------------------------
# Pool / scheduling integration
# ---------------------------------------------------------------------------


def test_spec_pool_shares_page_ids_and_drains(models):
    cfg, params = models["cfg"], models["params"]
    eng = _engine(cfg, params, spec_k=3, spec_draft=models["draft"],
                  prefix_cache=False)
    pool = eng.pool
    assert pool.draft_kv is not None
    # one page-id space: the draft leaf has the same page axis
    assert pool.draft_kv.shape[1] == pool.kv.shape[1]
    assert pool.draft_kv.shape[0] == models["draft"].cfg.model.num_layers
    # the logical view of a page, whatever the row: [L, n, page, nkv, d]
    dm = models["draft"].cfg.model
    dk, dv = pool.logical_kv([1, 2], draft=True)
    assert dk.shape == dv.shape == (
        dm.num_layers, 2, pool.page_size, dm.num_attention_heads_kv,
        dm.kv_channels)
    _run(eng, _greedy_jobs())
    assert np.all(pool.refcounts == 0)
    assert pool.num_free == pool.num_pages - 1  # cache off: all pages back
    assert eng._classes[0].committed == 0


def test_spec_requires_draft_and_chunked_prefill(models):
    cfg, params = models["cfg"], models["params"]
    with pytest.raises(ValueError, match="draft"):
        _engine(cfg, params, spec_k=2)


def test_draft_compat_rejected(models):
    cfg = models["cfg"]
    from megatron_llm_tpu.models import make_config

    bad = make_config(
        "llama2", num_layers=1, hidden_size=32, num_attention_heads=2,
        num_attention_heads_kv=2, ffn_hidden_size=64, seq_length=128,
        max_position_embeddings=256, vocab_size=VOCAB + 1,
        params_dtype="float32", use_flash_attn=False)
    with pytest.raises(ValueError, match="vocab"):
        check_draft_compat(cfg, bad, max_seq=128)
    short = make_config(
        "llama2", num_layers=1, hidden_size=32, num_attention_heads=2,
        num_attention_heads_kv=2, ffn_hidden_size=64, seq_length=64,
        max_position_embeddings=64, vocab_size=VOCAB,
        params_dtype="float32", use_flash_attn=False)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        check_draft_compat(cfg, short, max_seq=128)


def test_parse_draft_spec():
    fam, ov, load = parse_draft_spec(
        "llama2:num_layers=2,hidden_size=256,use_flash_attn=false")
    assert fam == "llama2"
    assert ov == {"num_layers": 2, "hidden_size": 256,
                  "use_flash_attn": False}
    assert load is None
    fam, ov, load = parse_draft_spec("llama2:num_layers=1@/ckpt/d")
    assert load == "/ckpt/d" and ov == {"num_layers": 1}
    with pytest.raises(ValueError, match="key=val"):
        parse_draft_spec("llama2:num_layers")


def test_engine_resolves_draft_from_config_flags(models):
    """The server path: --spec_k/--spec_draft land in cfg.inference and
    the engine resolves the draft spec string itself (random-init branch),
    still lossless vs spec_k=0."""
    import copy

    cfg = copy.deepcopy(models["cfg"])
    cfg.inference.spec_k = 2
    cfg.inference.spec_draft = (
        "llama2:num_layers=1,hidden_size=32,num_attention_heads=2,"
        "num_attention_heads_kv=2,ffn_hidden_size=64")
    eng = ContinuousBatchingEngine(cfg, models["params"], None,
                                   max_slots=2, max_seq=128)
    assert eng.spec_k == 2
    assert eng.draft_cfg.model.num_layers == 1
    assert eng.draft_cfg.model.vocab_size == VOCAB  # inherited from target
    jobs = [([4, 8, 15, 16], 10, dict(top_k=1, termination_id=10 ** 9))]
    res = _run(eng, jobs)
    base = _run(_engine(models["cfg"], models["params"], max_slots=2),
                jobs)
    assert_same_generations(res, base)


def test_adaptive_depth_shrinks_on_low_acceptance(models):
    """The random draft accepts ~0: adaptive mode must collapse per-slot
    depth toward 1, spending far fewer draft tokens than fixed depth."""
    cfg, params = models["cfg"], models["params"]
    jobs = [([3, 1, 4, 1, 5], 24, dict(top_k=1, termination_id=10 ** 9))]
    fixed = _engine(cfg, params, spec_k=4, spec_draft=models["draft"],
                    spec_adaptive=False)
    _run(fixed, jobs)
    adaptive = _engine(cfg, params, spec_k=4, spec_draft=models["draft"],
                       spec_adaptive=True)
    reqs = [adaptive.submit(p, n, **kw) for p, n, kw in jobs]
    adaptive.run_until_idle()
    for r in reqs:
        r.result(timeout=60)
    assert adaptive.spec_draft_tokens < fixed.spec_draft_tokens
    assert reqs[0]._spec_ema < 0.5
    # losslessness is depth-independent: same tokens either way
    assert fixed.spec_emitted_tokens == adaptive.spec_emitted_tokens


def test_spec_under_slo_policy_preemption(models):
    """Scheduler-policy interaction: under the slo policy a hi-priority
    burst preempts speculating batch slots — draft pages release through
    the same trie-park path, and the preempted requests' outputs stay
    the plain-decode stream."""
    cfg, params = models["cfg"], models["params"]
    kw = dict(top_k=1, termination_id=10 ** 9)
    eng = _engine(cfg, params, max_slots=2, sched_policy="slo",
                  spec_k=3, spec_draft=models["draft"])
    lo = [eng.submit([2 + i] * 8, 40, priority=2, **kw) for i in range(2)]
    while sum(r._t_first > 0 for r in lo) < 2:
        eng.step()
    hi = [eng.submit([9, 9, 9 + i], 8, priority=0,
                     ttft_deadline_ms=60000.0, **kw) for i in range(2)]
    eng.run_until_idle()
    for r in hi:
        r.result(timeout=60)
    assert eng.preemptions >= 1
    base = _engine(cfg, params, max_slots=2)
    ref = [base.submit([2 + i] * 8, 40, **kw) for i in range(2)]
    base.run_until_idle()
    assert_same_generations([a.result(timeout=60) for a in lo],
                            [b.result(timeout=60) for b in ref])


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------


def test_spec_metrics_and_health(models):
    cfg, params = models["cfg"], models["params"]
    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.observability import registry as obs_registry

    obs_registry.set_publishing(True)
    try:
        eng = _engine(cfg, params, spec_k=2, spec_draft=models["draft"])
        _run(eng, _greedy_jobs(n_new=6)[:2])
        stats = eng.spec_stats()
        assert stats["enabled"] and stats["spec_k"] == 2
        assert stats["draft_tokens"] > 0
        assert stats["acceptance_rate"] is not None
        text = obs_registry.get_registry().render()
        for name in ("mlt_engine_spec_draft_tokens_total",
                     "mlt_engine_spec_accepted_tokens_total",
                     "mlt_engine_spec_acceptance_ratio",
                     "mlt_engine_spec_accepted_length",
                     "mlt_engine_spec_k"):
            assert name in text, f"{name} missing from /metrics"
        server = MegatronServer(eng)
        health = server.health()
        assert health["spec"]["enabled"] is True
        assert health["spec"]["spec_k"] == 2
        # a non-speculating engine reports spec disabled
        plain = _engine(cfg, params)
        assert MegatronServer(plain).health()["spec"] == {"enabled": False}
    finally:
        # restore the PROCESS DEFAULT (publishing on) — restoring False
        # left every later-ordered test with a dead registry (latent
        # order dependence, exposed by non-alphabetical test selection)
        obs_registry.set_publishing(True)
