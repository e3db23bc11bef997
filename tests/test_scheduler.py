"""Serving control plane tests (ISSUE 7, generation/scheduling/).

Gates: (1) the fcfs policy — the default — is the pre-policy engine,
token-for-token: same tokens AND log-probs as the dense single-stream
reference, strict submission-order admission, nothing preempted or shed;
(2) preemption-by-page-release resumes BITWISE through the prefix cache
(tokens + log-probs, greedy and sampled, any cut point); (3) the
commitment ledger + page-state invariants hold through preempt/resume
churn (free + evictable always covers the admitted worst case); (4) the
priority policy's aging bound ends starvation; (5) the slo policy admits
earliest-deadline-first and sheds unmeetable deadlines; (6) admission
control is metrics-driven: EMA-drain Retry-After on 503s, per-priority
queue bounds, and the per-priority queue gauges update from one
scheduler-owned point.
"""

import time
from collections import Counter

import numpy as np
import pytest

import jax

from megatron_llm_tpu.generation import (
    ContinuousBatchingEngine,
    EngineOverloaded,
    RequestShed,
    get_policy,
)
from megatron_llm_tpu.generation.pools import NULL_PAGE
from megatron_llm_tpu.generation.scheduling import (
    FcfsPolicy,
    PriorityPolicy,
    SchedulerState,
    SloPolicy,
    available_policies,
)
from megatron_llm_tpu.generation.server import MegatronServer
from megatron_llm_tpu.models import init_model_params, make_config
from megatron_llm_tpu.observability import registry as obs_registry

from tests.parity import (
    assert_greedy_match_dense,
    assert_logprobs_close,
    assert_same_generations,
    generations,
    run_jobs,
    serve_alone,
)

VOCAB = 67
GKW = dict(top_k=1, termination_id=10 ** 9)


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


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(cfg, params, None, **kw)


def _prompt(n, off=0):
    return [2 + ((i + off) * 7) % 60 for i in range(n)]


def _drain(eng, reqs, timeout=60):
    eng.run_until_idle()
    return [r.result(timeout=timeout) for r in reqs]


# ---------------------------------------------------------------------------
# fcfs: the pre-policy engine (same tokens, log-probs to a few ulps:
# tests/parity.py)
# ---------------------------------------------------------------------------


def test_policy_registry():
    assert {"fcfs", "priority", "slo"} <= set(available_policies())
    assert get_policy("fcfs") is FcfsPolicy
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        get_policy("lottery")


def test_fcfs_bitwise_parity_vs_monolithic_reference(toy_model):
    """Default engine (fcfs policy, chunked+cached) against the two
    references of tests/parity.py, neither of which has a scheduler: its
    greedy jobs are the dense single-stream path's on tokens AND
    log-probs, and every job (the sampled one too) is what the same
    engine gives the request alone — queueing behind two slots under the
    policy layer changed nothing."""
    cfg, params = toy_model
    jobs = [(_prompt(n, n), 10, dict(seed=n, **GKW)) for n in (3, 20, 40)]
    jobs.append((_prompt(24, 5), 10,
                 dict(temperature=0.8, top_p=0.9, seed=7,
                      termination_id=10 ** 9)))

    def make():
        return _engine(cfg, params, sched_policy="fcfs")

    fcfs = make()
    assert isinstance(fcfs.policy, FcfsPolicy)
    got = run_jobs(fcfs, jobs)
    assert assert_greedy_match_dense(cfg, params, jobs, got) == 3
    assert_same_generations(generations(serve_alone(make, jobs)),
                            generations(got))
    assert fcfs.preemptions == 0 and fcfs.shed_requests == 0


def test_fcfs_admission_is_submission_order(toy_model):
    """One slot, three queued requests: first tokens land in submit
    order — the fcfs head blocks, nothing skips it."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1)
    reqs = [eng.submit(_prompt(8, i), 4, seed=i, **GKW) for i in range(3)]
    _drain(eng, reqs)
    firsts = [r._t_first for r in reqs]
    assert firsts == sorted(firsts)


# ---------------------------------------------------------------------------
# Preemption by page release: bitwise resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cut,cache", [(1, True), (17, True), (33, True),
                                       (13, False)])
def test_preempt_resume_bitwise(toy_model, cut, cache):
    """Preempt a decoding request mid-stream, let it resume: tokens and
    log-probs are what an uninterrupted run produces.  With the
    cache on, resume re-matches the SAME physical pages out of the trie
    (near-zero recompute); with it off, the chunked re-prefill recomputes
    the tail — both land on the same stream (the PR 5 grid-aligned chunk
    invariant)."""
    cfg, params = toy_model
    prompt = _prompt(30)
    ref_eng = _engine(cfg, params)
    ref = ref_eng.submit(prompt, 40, seed=5, **GKW)
    (t_ref, lp_ref), = _drain(ref_eng, [ref])

    eng = _engine(cfg, params, prefix_cache=cache)
    hits0 = eng.prefix_hit_tokens
    req = eng.submit(prompt, 40, seed=5, **GKW)
    while len(req.generated) < cut:
        eng.step()
    assert eng.preempt(req)
    assert req._phase == "queued" and not req._mem[0].pages
    (t, lp), = _drain(eng, [req])
    assert t == t_ref
    assert_logprobs_close(lp, lp_ref)
    assert eng.preemptions == 1
    if cache:
        # resume matched the parked pages back out of the trie
        assert eng.prefix_hit_tokens - hits0 >= (cut // eng.page_size) \
            * eng.page_size


def test_preempt_resume_bitwise_sampled(toy_model):
    """The pinned PRNG key + resumed step counter continue the sampling
    stream exactly: a preempted temperature/top-p request matches its
    uninterrupted twin."""
    cfg, params = toy_model
    prompt = _prompt(30)
    kw = dict(temperature=0.8, top_p=0.9, seed=9, termination_id=10 ** 9)
    ref_eng = _engine(cfg, params)
    ref = ref_eng.submit(prompt, 30, **kw)
    (t_ref, lp_ref), = _drain(ref_eng, [ref])

    eng = _engine(cfg, params)
    req = eng.submit(prompt, 30, **kw)
    while len(req.generated) < 11:
        eng.step()
    assert eng.preempt(req)
    (t, lp), = _drain(eng, [req])
    assert t == t_ref
    assert_logprobs_close(lp, lp_ref)


def _assert_invariants(eng):
    """Page states exact + the commitment ledger covers the admitted
    worst case (the deadlock-freedom invariant, now under preemption)."""
    pool = eng.pool
    holders = Counter(p for r in eng._slots if r is not None
                      for p in r._mem[0].pages)
    free = set(pool._free)
    assert NULL_PAGE not in free and holders.get(NULL_PAGE, 0) == 0
    for p in range(1, pool.num_pages):
        assert pool.refcounts[p] == holders.get(p, 0)
        if p in free:
            assert pool.refcounts[p] == 0 and p not in pool.cached
    cached_idle = sum(1 for p in pool.cached if pool.refcounts[p] == 0)
    assert len(holders) + pool.num_free + cached_idle == pool.num_pages - 1
    assert pool.num_available >= eng._classes[0].committed + eng.page_watermark
    # queued requests (incl. preempted ones) hold nothing
    for r in eng._queue:
        assert not any(m.pages for m in r._mem) and r._slot == -1


def test_ledger_and_page_invariants_under_preemption_churn(toy_model):
    """Priority traffic through a tight pool with forced + policy-driven
    preemptions: the ledger and page-state invariants hold at every step
    and the pool drains whole."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, page_size=16, num_pages=17,
                  sched_policy="priority", page_watermark=1)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(_prompt(int(rng.integers(8, 40)), i),
                       int(rng.integers(4, 24)),
                       priority=int(rng.integers(0, 3)), seed=i, **GKW)
            for i in range(10)]
    steps = 0
    while True:
        n = eng.step()
        _assert_invariants(eng)
        # force extra churn: preempt a random decoder every few steps
        if steps % 7 == 3:
            decoding = [r for r in eng._slots
                        if r is not None and r._phase == "decode"]
            if decoding:
                eng.preempt(decoding[0])
                _assert_invariants(eng)
        steps += 1
        if n == 0 and not eng._queue:
            break
        assert steps < 5000
    for r in reqs:
        toks, _ = r.result(timeout=5)
        assert len(r.generated) == r.max_new_tokens
    assert eng.preemptions >= 1
    assert int(eng.pool.refcounts.sum()) == 0
    assert eng._classes[0].committed == 0
    assert eng.pool.num_free + len(eng.pool.cached) == eng.pool.num_pages - 1


# ---------------------------------------------------------------------------
# priority: ordering, aging bound, preemption value rule
# ---------------------------------------------------------------------------


def _fake_req(prio=1, submitted=0.0, seqno=0, generated=0, t_first=0.0,
              ttft_ms=None, tpot_ms=None):
    class R:
        pass

    r = R()
    r.priority = prio
    r.ttft_deadline_ms = ttft_ms
    r.tpot_deadline_ms = tpot_ms
    r.return_log_probs = False
    r.generated = [0] * generated
    r._t_submit = submitted
    r._t_first = t_first
    r._step = generated
    r._seqno = seqno
    return r


def _state(now=100.0, **kw):
    kw.setdefault("ema_tick_s", None)
    kw.setdefault("ema_retire_s", None)
    kw.setdefault("free_slots", 0)
    kw.setdefault("queue_depth", 0)
    return SchedulerState(now=now, **kw)


def test_priority_aging_bound_in_ordering():
    """A class-p request outranks fresh class-0 arrivals after waiting at
    most p * aging_s seconds — the starvation bound, deterministically."""
    pol = PriorityPolicy(aging_s=5.0)
    old_low = _fake_req(prio=3, submitted=0.0, seqno=1)
    # before the bound (waited 10s < 3 * 5s): a just-arrived class-0 wins
    fresh_hi = _fake_req(prio=0, submitted=10.0, seqno=2)
    order = pol.admission_order([old_low, fresh_hi], _state(now=10.0))
    assert order[0] is fresh_hi
    # after the bound (waited 16s > 15s): the aged request wins
    fresh_hi = _fake_req(prio=0, submitted=16.0, seqno=3)
    order = pol.admission_order([old_low, fresh_hi], _state(now=16.0))
    assert order[0] is old_low


def test_priority_starvation_bound_end_to_end(toy_model):
    """Engine-level: a low-priority request older than its aging bound
    admits ahead of a fresher high-priority one."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1, sched_policy="priority")
    eng.policy.aging_s = 0.02  # 3-class bound = 60ms
    low = eng.submit(_prompt(8), 4, priority=3, seed=1, **GKW)
    time.sleep(0.1)
    hi = eng.submit(_prompt(8, 3), 4, priority=0, seed=2, **GKW)
    _drain(eng, [low, hi])
    assert low._t_first < hi._t_first, "aged request still starved"


def test_priority_preemption_strictly_lower_value(toy_model):
    """A high-priority arrival evicts a lower-priority decoder (slots
    full), the victim resumes and still finishes; equal-priority arrivals
    never preempt (no livelock)."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1, sched_policy="priority")
    low = eng.submit(_prompt(20), 40, priority=2, seed=1, **GKW)
    while len(low.generated) < 5:
        eng.step()
    peer = eng.submit(_prompt(20, 3), 4, priority=2, seed=2, **GKW)
    for _ in range(4):
        eng.step()
    assert eng.preemptions == 0, "equal priority must not preempt"
    hi = eng.submit(_prompt(20, 9), 4, priority=0, seed=3, **GKW)
    for _ in range(4):
        eng.step()
    assert eng.preemptions == 1
    assert low._preemptions == 1
    _drain(eng, [low, peer, hi])
    assert len(low.generated) == 40
    assert hi._t_first < peer._t_first  # hi jumped the aged-equal queue


# ---------------------------------------------------------------------------
# slo: EDF order, shedding, victim rule
# ---------------------------------------------------------------------------


def test_slo_edf_admission_order(toy_model):
    """One slot, three deadlined requests submitted out of deadline
    order: first tokens land earliest-deadline-first, best-effort last."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1, sched_policy="slo")
    c = eng.submit(_prompt(8, 2), 3, ttft_deadline_ms=50000, seed=3, **GKW)
    be = eng.submit(_prompt(8, 9), 3, seed=4, **GKW)  # no deadline
    a = eng.submit(_prompt(8, 0), 3, ttft_deadline_ms=10000, seed=1, **GKW)
    b = eng.submit(_prompt(8, 1), 3, ttft_deadline_ms=20000, seed=2, **GKW)
    _drain(eng, [a, b, c, be])
    assert a._t_first < b._t_first < c._t_first < be._t_first


def test_slo_sheds_unmeetable_deadline(toy_model):
    """A queued request whose TTFT deadline already passed is shed with a
    retryable RequestShed instead of wasting pool pages; live-deadline
    traffic is untouched."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1, sched_policy="slo")
    dead = eng.submit(_prompt(16), 4, ttft_deadline_ms=0.01, seed=1, **GKW)
    time.sleep(0.05)
    ok = eng.submit(_prompt(16, 3), 4, ttft_deadline_ms=60000, seed=2,
                    **GKW)
    eng.run_until_idle()
    with pytest.raises(RequestShed, match="deadline already passed"):
        dead.result(timeout=5)
    assert dead.shed and dead.shed_retry_after >= 1.0
    ok.result(timeout=60)
    assert eng.shed_requests == 1
    assert eng.scheduler_stats()["shed"] == 1


def test_slo_sheds_on_predicted_queue_wait():
    """Policy-level: with a retirement EMA, a deadline that the predicted
    EDF queue wait overshoots is shed before it ever holds pages."""
    pol = SloPolicy()
    # EDF positions 0 and 1; 2s per retirement
    near = _fake_req(submitted=0.0, seqno=1, ttft_ms=10000)
    tight = _fake_req(submitted=0.0, seqno=2, ttft_ms=11000)
    st = _state(now=10.0, ema_retire_s=2.0)
    shed = pol.shed([near, tight], st)
    # near: eta position 0 -> meets; tight: position 1 -> 10+2 > 11 miss
    assert [(r is tight) for r, _ in shed] == [True]
    assert "predicted queue wait" in shed[0][1]
    # best-effort requests never shed
    assert pol.shed([_fake_req(seqno=3)], st) == []


def test_slo_victim_rule():
    """Preemption victims: best-effort decoders first (inf obligation);
    a candidate without a deadline preempts nobody."""
    pol = SloPolicy()
    cand = _fake_req(seqno=1, ttft_ms=1000, submitted=99.0)
    be_decoder = _fake_req(seqno=2, generated=5, t_first=90.0)
    tight_decoder = _fake_req(seqno=3, generated=5, t_first=90.0,
                              tpot_ms=1.0)
    st = _state(now=100.0)
    assert pol.preempt_victim(cand, [be_decoder, tight_decoder],
                              st) is be_decoder
    no_dl = _fake_req(seqno=4)
    assert pol.preempt_victim(no_dl, [be_decoder], st) is None
    # a decoding request keeps its TTFT deadline as its value: a later
    # arrival from the same burst (later deadline) cannot bounce it —
    # no same-class preemption churn
    same_burst = _fake_req(seqno=5, ttft_ms=1000, submitted=98.0,
                           generated=3, t_first=98.5)
    assert pol.preempt_victim(cand, [same_burst], st) is None


# ---------------------------------------------------------------------------
# Admission control: EMA Retry-After, quotas, centralized queue gauges
# ---------------------------------------------------------------------------


def test_retry_after_from_ema_drain(toy_model):
    """EngineOverloaded.retry_after = queue depth x the EMA retirement
    interval (clamped to [1, 60]) — measured, not the old constant —
    and the structured info rides into the server's 503 body."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=1, max_queue=3)
    eng._ema_retire_s = 2.5
    for i in range(3):
        eng.submit(_prompt(8, i), 2, seed=i, **GKW)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(_prompt(8, 9), 2, **GKW)
    assert ei.value.retry_after == pytest.approx(3 * 2.5)
    assert ei.value.info["queued"] == 3
    assert ei.value.info["policy"] == "fcfs"
    eng.run_until_idle()
    # clamps: no signal -> 1.0; huge backlog -> 60
    assert _engine(cfg, params)._drain_eta(5) == 1.0
    eng._ema_retire_s = 100.0
    assert eng._drain_eta(5) == 60.0


def test_server_503_body_carries_drain_estimate():
    """server.handle_request spreads EngineOverloaded.info into the 503
    body alongside retry_after (the Retry-After header source)."""

    class StuffedEngine:
        lock = None

        def submit(self, *a, **kw):
            raise EngineOverloaded("request queue full (3 waiting)",
                                   retry_after=7.5,
                                   info={"queued": 3, "policy": "slo"})

        def generate_and_post_process(self, *a, **kw):
            self.submit()

        def start(self):
            pass

        def stop(self):
            pass

    srv = MegatronServer(StuffedEngine())
    code, body = srv.handle_request({"prompts": ["x"],
                                     "tokens_to_generate": 4})
    assert code == 503
    assert body["retry_after"] == 7.5
    assert body["queued"] == 3 and body["policy"] == "slo"


def test_server_maps_shed_to_503():
    class SheddingEngine:
        def submit(self, *a, **kw):
            pass

        def generate_and_post_process(self, *a, **kw):
            raise RequestShed("request shed: ttft deadline already passed",
                              retry_after=2.0)

        def start(self):
            pass

        def stop(self):
            pass

    srv = MegatronServer(SheddingEngine())
    code, body = srv.handle_request({"prompts": ["x"],
                                     "tokens_to_generate": 4})
    assert code == 503
    assert body["shed"] is True and body["retry_after"] == 2.0


def test_server_validates_scheduling_fields():
    srv = MegatronServer(object())
    base = {"prompts": ["x"], "tokens_to_generate": 4}
    code, body = srv.handle_request({**base, "priority": "high"})
    assert code == 400 and "priority must be an integer" in body["error"]
    code, body = srv.handle_request({**base, "priority": 11})
    assert code == 400
    code, body = srv.handle_request({**base, "ttft_deadline_ms": -5})
    assert code == 400 and "ttft_deadline_ms" in body["error"]
    code, body = srv.handle_request({**base, "tpot_deadline_ms": True})
    assert code == 400 and "tpot_deadline_ms" in body["error"]


def test_per_priority_queue_bounds(toy_model):
    """--sched_quota bounds each class independently of the global
    bound: an over-quota class 503s while other classes still enqueue."""
    cfg, params = toy_model
    old = cfg.inference.sched_quota
    cfg.inference.sched_quota = "0:2"
    try:
        eng = _engine(cfg, params, max_slots=1, max_queue=16)
    finally:
        cfg.inference.sched_quota = old
    reqs = [eng.submit(_prompt(8, i), 2, priority=0, seed=i, **GKW)
            for i in range(2)]
    with pytest.raises(EngineOverloaded, match="priority-0 queue full"):
        eng.submit(_prompt(8, 9), 2, priority=0, **GKW)
    reqs.append(eng.submit(_prompt(8, 5), 2, priority=1, **GKW))
    _drain(eng, reqs)


def test_queued_gauges_centralized_per_priority(toy_model):
    """mlt_engine_queued_requests carries per-priority labels from the
    single scheduler-owned update point, agrees with the total, and
    drops to zero after the queue drains."""
    cfg, params = toy_model
    reg = obs_registry.get_registry()
    eng = _engine(cfg, params, max_slots=1)
    reqs = [eng.submit(_prompt(8, i), 2, priority=p, seed=i, **GKW)
            for i, p in enumerate((0, 0, 2))]
    total = reg.gauge("mlt_engine_queued_requests").value
    p0 = reg.gauge("mlt_engine_queued_requests",
                   labels={"priority": "0"}).value
    p2 = reg.gauge("mlt_engine_queued_requests",
                   labels={"priority": "2"}).value
    assert total == p0 + p2 and p0 == 2 and p2 == 1
    rendered = reg.render()
    assert 'mlt_engine_queued_requests{priority="0"} 2' in rendered
    _drain(eng, reqs)
    assert reg.gauge("mlt_engine_queued_requests").value == 0
    assert reg.gauge("mlt_engine_queued_requests",
                     labels={"priority": "0"}).value == 0
    assert reg.counter("mlt_engine_preemptions_total").value >= 0


def test_health_scheduler_payload(toy_model):
    cfg, params = toy_model
    eng = _engine(cfg, params, sched_policy="slo")
    srv = MegatronServer(eng)
    info = srv.health()
    sched = info["scheduler"]
    assert sched["policy"] == "slo"
    assert {"queued", "queued_by_priority", "preemptions", "shed",
            "deadline_misses", "retry_after_s"} <= set(sched)


def test_deadline_miss_accounting(toy_model):
    """A retired request that blew its TTFT deadline lands in the miss
    counters (fcfs still serves it; slo would have shed it)."""
    cfg, params = toy_model
    reg = obs_registry.get_registry()
    before = reg.counter("mlt_engine_deadline_miss_total",
                         labels={"kind": "ttft"}).value
    eng = _engine(cfg, params)  # fcfs: never sheds, so the miss retires
    req = eng.submit(_prompt(16), 2, ttft_deadline_ms=0.001, seed=1, **GKW)
    time.sleep(0.01)
    _drain(eng, [req])
    assert eng.deadline_misses == 1
    after = reg.counter("mlt_engine_deadline_miss_total",
                        labels={"kind": "ttft"}).value
    assert after == before + 1
