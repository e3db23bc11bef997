"""The ragged step runs one tick ahead of the host (ISSUE 30).

``ContinuousBatchingEngine._step_ragged`` dispatches tick N+1 before it has
fetched tick N, and applies N while the device runs N+1.  What must hold:

1. **lossless** — tokens and log-probs are the references' of
   tests/parity.py (the dense single-stream path for greedy jobs, the same
   engine serving the request alone for every job), under a backlog larger
   than the slots, greedy and seeded sampling, mixed prompt lengths;
2. **finishes** — a stop token's overrun row is dropped and its slot and
   pages are re-admitted while that row is still in flight; a row whose
   budget the tick in flight spends (``max_new_tokens``, ``max_seq``, at a
   page boundary) is launched dead, never past its granted pages;
3. **preemption** at the lag, by the policy and by ``preempt()``: the
   dropped tokens are drawn again, bit for bit;
4. **handoff** — a ``prefill_only`` request parks only after the tick that
   wrote its last page was fetched;
5. **lag 0 where the host cannot predict** — a speculative engine applies
   every tick at once (``mlt_engine_tick_apply_lag_total{lag="0"}``), and so
   does a step that ran a scoring chunk;
6. **bookkeeping** — ``engine-launch tick=n+1`` opens before ``engine-fetch
   tick=n``; ``step()`` / ``run_until_idle`` never report idle with a tick
   in flight; ``_ema_tick_s`` reads one tick, not the two a launch-to-apply
   interval now spans.
"""

import collections
import time

import jax
import pytest

from megatron_llm_tpu.generation import ContinuousBatchingEngine, DraftModel
from megatron_llm_tpu.generation import engine as engine_mod
from megatron_llm_tpu.generation import generation as gen
from megatron_llm_tpu.observability import registry as obs_registry
from megatron_llm_tpu.observability import trace as obs_trace

from tests.parity import (
    assert_greedy_match_dense,
    assert_same_generations,
    dense_greedy,
    generations,
    run_jobs,
    serve_alone,
)

VOCAB = 67
PAGE = 16
GREEDY = dict(top_k=1, termination_id=10 ** 9)


@pytest.fixture(scope="module")
def models():
    from megatron_llm_tpu.models import init_model_params, make_config

    def mk(layers, hidden, heads, nkv, ffn):
        return make_config(
            "llama2", num_layers=layers, hidden_size=hidden,
            num_attention_heads=heads, num_attention_heads_kv=nkv,
            ffn_hidden_size=ffn, seq_length=256,
            max_position_embeddings=256, vocab_size=VOCAB,
            hidden_dropout=0.0, attention_dropout=0.0,
            params_dtype="float32", use_flash_attn=False,
        )

    cfg = mk(2, 64, 4, 2, 128)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    dcfg = mk(1, 32, 2, 2, 64)
    dparams = init_model_params(dcfg, jax.random.PRNGKey(1))
    return {"cfg": cfg, "params": params,
            "draft": DraftModel(dcfg, dparams)}


def _engine(models, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(models["cfg"], models["params"], None,
                                    **kw)


def _prompt(n, salt=0):
    return [2 + (salt * 11 + j * 7) % 60 for j in range(n)]


def _lag_counts():
    reg = obs_registry.get_registry()
    return {lag: reg.counter("mlt_engine_tick_apply_lag_total",
                             labels={"lag": lag}).value
            for lag in ("0", "1")}


def _lag_delta(before):
    after = _lag_counts()
    return {k: after[k] - before[k] for k in after}


def _backlog_jobs():
    """Ten jobs on four slots: prompts of 3 to 100 tokens (one chunk, two
    chunks, a shared prefix), greedy and seeded sampling."""
    shared = _prompt(48)
    jobs = [(_prompt(3, i), 9 + i, dict(GREEDY)) for i in range(3)]
    jobs += [(shared + _prompt(20 + 17 * i, 5 + i), 8, dict(GREEDY))
             for i in range(2)]
    jobs += [(_prompt(100, 3), 6, dict(GREEDY)),
             (list(shared), 7, dict(GREEDY))]
    jobs += [(_prompt(30 + 9 * i, 20 + i), 10,
              dict(temperature=0.9, top_k=7, seed=42 + i,
                   termination_id=10 ** 9)) for i in range(3)]
    return jobs


# ---------------------------------------------------------------------------
# 1. lossless under a backlog
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def backlog_alone(models):
    """Each job of the backlog served alone: the tight reference of every
    test that runs the backlog (scheduling keys change no token)."""
    return generations(serve_alone(lambda: _engine(models), _backlog_jobs()))


def _pages_accounted(eng):
    """Every page is free, referenced or cached idle, whatever is in
    flight; and no page is granted twice: a page held by several live
    requests is one the prefix cache shares, each holder a reference."""
    pool = eng.pool
    referenced = int((pool.refcounts > 0).sum())
    assert pool.num_free + referenced + pool.num_evictable == \
        pool.num_pages - 1
    holders = collections.Counter(
        p for r in eng._slots if r is not None for p in r._mem[0].pages)
    assert all(pool.refcounts[p] == n for p, n in holders.items()), holders
    assert referenced == len(holders)
    private = [p for p, n in holders.items() if p not in pool.cached]
    assert all(holders[p] == 1 for p in private)


def _all_pages_back(eng, models, **kw):
    """Nothing in flight, and every page free or cached idle."""
    assert not eng._inflight
    cached = len(eng.cache) if eng.cache is not None else 0
    assert eng.pool.num_evictable == cached
    assert eng.pool.num_free == _engine(models, **kw).pool.num_free - cached


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["cache_on", "cache_off"])
def test_backlog_matches_both_references(models, backlog_alone,
                                         prefix_cache):
    jobs = _backlog_jobs()
    before = _lag_counts()
    eng = _engine(models, prefix_cache=prefix_cache)
    ticks0 = eng.ticks
    reqs = run_jobs(eng, jobs)
    n = eng.ticks - ticks0
    lag = _lag_delta(before)
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == 7
    assert_same_generations(backlog_alone, generations(reqs),
                            "a backlog against each request alone")
    # the mechanism ran: nearly every tick was applied behind its successor
    assert lag["0"] + lag["1"] == n
    assert lag["1"] >= 0.8 * n, (lag, n)
    assert (eng.cache is not None) == prefix_cache
    _all_pages_back(eng, models)


@pytest.mark.parametrize("policy", ["priority", "slo"])
def test_backlog_under_a_policy_with_a_tick_in_flight(models, backlog_alone,
                                                      policy):
    """Admission order, the prefill budget, preemption and shedding are
    the policy's, and all of them now land with a tick in flight: two
    slots and a pool of exactly their pages under the backlog (the prefix
    cache's idle pages are evicted to grant), the pages accounted for at
    every step, every request still its own alone."""
    jobs = []
    for i, (p, n, kw) in enumerate(_backlog_jobs()):
        key = (dict(priority=i % 3) if policy == "priority"
               else dict(ttft_deadline_ms=60_000.0 + 10_000 * i))
        jobs.append((p, n, dict(kw, **key)))
    before = _lag_counts()
    eng = _engine(models, max_slots=2, sched_policy=policy)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    busy = 0            # steps that admitted or planned beside a tick
    assert eng.pool.num_pages == 2 * (128 // PAGE) + 1
    while not all(r.finished for r in reqs):
        busy += bool(eng._inflight) and bool(eng._queue or eng._prefill_q)
        eng.step()
        _pages_accounted(eng)
    eng.run_until_idle()
    assert busy and _lag_delta(before)["1"]
    assert eng.failures == 0
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == 7
    assert_same_generations(backlog_alone, generations(reqs),
                            f"the backlog under {policy}")
    _all_pages_back(eng, models, max_slots=2)


def test_a_scoring_chunk_step_applies_at_once(models):
    """return_log_probs prompts take the teacher-forced chunk beside the
    tick; a step that ran one lands its tick before it returns."""
    jobs = [(_prompt(40, 1), 6, dict(GREEDY, return_log_probs=True)),
            (_prompt(5, 2), 12, dict(GREEDY))]
    eng = _engine(models)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    scored = 0
    while not all(r.finished for r in reqs):
        launches0 = eng.tick_launches
        eng.step()
        if eng.last_tick_launches == 2:  # the tick and a scoring chunk
            scored += 1
            assert not eng._inflight
        assert eng.tick_launches - launches0 == eng.last_tick_launches
    assert scored >= 1
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == 2


# ---------------------------------------------------------------------------
# 2. finishes
# ---------------------------------------------------------------------------


def test_stop_token_overrun_row_is_dropped_and_slot_reused(models):
    """A stop token ends a row one tick after its successor was launched:
    that overrun row is dropped, and the slot and the pages go to the next
    request while it is still in flight."""
    prompt = _prompt(20, 4)
    # a sampled stream (the toy model's greedy one repeats itself): the
    # first seed whose tokens 3..9 hold one that did not occur before
    for seed in range(20):
        kw = dict(temperature=0.9, top_k=7, seed=seed)
        stream = run_jobs(_engine(models), [
            (prompt, 12, dict(kw, termination_id=10 ** 9))])[0].generated
        k = next((i for i in range(3, 10) if stream[i] not in stream[:i]),
                 None)
        if k is not None:
            break
    stop_kw = dict(kw, termination_id=stream[k])
    jobs = [(prompt, 12, stop_kw)] + [
        (_prompt(18 + 5 * i, 30 + i), 10, dict(GREEDY)) for i in range(4)]
    eng = _engine(models, max_slots=2)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    stopper = reqs[0]
    overrun = reused = False
    slot = None
    while not all(r.finished for r in reqs):
        if stopper._phase == "decode":
            slot = stopper._slot
        eng.step()
        if stopper.finished and eng._inflight:
            rec = eng._inflight[-1]
            if any(r is stopper for r in rec.reqs):
                overrun = True  # launched before the stop token was seen
                held = eng._slots[slot]
                # admission runs before the next launch: step once more
                eng.step()
                reused = (eng._slots[slot] is not None
                          and eng._slots[slot] is not stopper
                          and eng._slots[slot] is not held)
    assert overrun and reused
    assert stopper.generated == stream[:k + 1]
    alone = serve_alone(lambda: _engine(models, max_slots=2), jobs)
    assert_same_generations(generations(alone), generations(reqs),
                            "a stop token under a backlog")
    assert not eng._inflight
    assert eng.pool.num_free == _engine(
        models, max_slots=2).pool.num_free - len(eng.cache)


@pytest.mark.parametrize("n_prompt,asked,n_out", [
    (100, 28, 28),   # ends at max_seq (128): the end of the last page
    (10, 22, 22),    # max_new_tokens ends it exactly on a page boundary
    (10, 23, 23),    # ... and one position into the next page
])
def test_budget_end_is_launched_dead(models, n_prompt, asked, n_out):
    """The tick in flight spends the row's budget: the next tick carries it
    as a dead row (null table), so nothing is written past its pages, the
    neighbour's bits do not move and every page comes back."""
    prompt = _prompt(n_prompt, 6)
    other = (_prompt(7, 8), 40, dict(GREEDY))
    eng = _engine(models)
    free0 = eng.pool.num_free
    req = eng.submit(prompt, asked, **GREEDY)
    oth = eng.submit(*other[:2], **other[2])
    granted = []
    while not req.finished:
        eng.step()
        granted.append(len(req._mem[0].pages))
    eng.run_until_idle()
    toks, _ = req.result(timeout=60)
    ref, _ = dense_greedy(models["cfg"], models["params"], prompt, n_out)
    assert toks == ref and len(req.generated) == n_out
    # never more pages than its sequence needs
    assert max(granted) <= -(-(n_prompt + n_out) // PAGE)
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], [other], [oth]) == 1
    assert not eng._inflight
    assert eng.pool.num_free == free0 - len(eng.cache)


@pytest.mark.parametrize("mode", ["termination_id", "eol", "double_eol"])
def test_every_stop_mode_fires_behind_a_launched_tick(models, monkeypatch,
                                                      mode):
    """The host alone applies the stop rules, one tick late: whichever rule
    ends a row, its next tick is already launched, that row is dropped,
    and the request is the one served alone.  (The toy vocabulary holds no
    GPT-2 EOL id: the two ids are set to tokens of the stream.)"""
    prompt = _prompt(20, 4)
    free = dict(termination_id=10 ** 9)
    for seed in range(20):
        kw = dict(temperature=0.9, top_k=7, seed=seed)
        stream = run_jobs(_engine(models), [
            (prompt, 12, dict(kw, **free))])[0].generated
        k = next((i for i in range(3, 10) if stream[i] not in stream[:i]),
                 None)
        if k is not None:
            break
    if mode == "termination_id":
        stop_kw = dict(kw, termination_id=stream[k])
    elif mode == "eol":
        monkeypatch.setattr(gen, "GPT2_EOL", stream[k])
        stop_kw = dict(kw, stop_on_eol=True)
    else:
        # a lone EOL before the stop: it would end an ``eol`` row only
        lone = next(t for i, t in enumerate(stream[:k])
                    if stream[i + 1] != t and (i == 0 or stream[i - 1] != t)
                    and t != prompt[-1])
        monkeypatch.setattr(gen, "GPT2_EOL", lone)
        monkeypatch.setattr(gen, "GPT2_DOUBLE_EOL", stream[k])
        stop_kw = dict(kw, stop_on_double_eol=True)
    jobs = [(prompt, 12, stop_kw), (_prompt(18, 30), 14, dict(GREEDY))]
    eng = _engine(models)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    stopper, overrun = reqs[0], False
    while not all(r.finished for r in reqs):
        eng.step()
        if stopper.finished and eng._inflight:
            overrun |= any(r is stopper for r in eng._inflight[-1].reqs)
    assert overrun, "the stop never fired behind a launched tick"
    assert stopper.generated == stream[:k + 1]
    if mode == "double_eol":
        assert gen.GPT2_EOL in stopper.generated[:-1]
    alone = serve_alone(lambda: _engine(models), jobs)
    assert_same_generations(generations(alone), generations(reqs),
                            f"a {mode} stop beside a decoding row")
    _all_pages_back(eng, models)


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["cache_on", "cache_off"])
def test_a_pool_just_large_enough_never_fails_a_grant(models, prefix_cache):
    """Four sequences that each end at ``max_seq`` need every page of the
    pool: the ledger admits them all, every grant made with a tick in
    flight is served, and the drain leaves every page free or cached
    idle."""
    jobs = [(_prompt(40, 40 + i), 88, dict(GREEDY)) for i in range(4)]
    eng = _engine(models, prefix_cache=prefix_cache)
    assert eng.pool.num_free == 4 * (128 // PAGE)   # and the null page
    grants, alloc = [], eng.pool.alloc

    def counted(n):
        got = alloc(n)
        grants.append((bool(eng._inflight), got is not None))
        return got

    eng.pool.alloc = counted
    reqs = run_jobs(eng, jobs)
    eng.pool.alloc = alloc
    assert eng.peak_active_slots == 4 and eng.failures == 0
    assert all(ok for _, ok in grants)
    # a page a sequence and boundary crossed while it decodes
    assert sum(flying for flying, _ in grants) >= 4 * 4
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == 4
    _all_pages_back(eng, models)


# ---------------------------------------------------------------------------
# 3. preemption at the lag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cut", [3, 4, 6])
def test_preempt_hook_with_a_tick_in_flight(models, cut):
    """``preempt()`` between two steps always finds a tick in flight: its
    row for the victim is dropped and the resume draws the token again."""
    def run(preempt_at):
        eng = _engine(models, sched_policy="fcfs")
        req = eng.submit(_prompt(48), 14, temperature=0.9, top_k=7, seed=11,
                         termination_id=10 ** 9)
        other = eng.submit(_prompt(3, 2), 9, **GREEDY)
        steps = in_flight = 0
        while not req.finished:
            eng.step()
            steps += 1
            if steps == preempt_at and req._phase == "decode":
                in_flight = any(r is req for rec in eng._inflight
                                for r in rec.reqs)
                assert eng.preempt(req)
        eng.run_until_idle()
        assert not eng._inflight
        return generations([req, other]), in_flight

    base, _ = run(10 ** 9)
    got, in_flight = run(cut)
    assert in_flight, "the preemption found no tick in flight"
    assert_same_generations(base, got, f"preempt() after step {cut}")


def test_policy_preemption_with_a_tick_in_flight(models):
    """The priority policy evicts a decoder during admission, which now
    runs while the victim's last tick is unfetched."""
    eng = _engine(models, max_slots=1, sched_policy="priority")
    low = eng.submit(_prompt(20), 24, priority=2, seed=1, **GREEDY)
    while len(low.generated) < 5:
        eng.step()
    assert any(r is low for rec in eng._inflight for r in rec.reqs)
    hi = eng.submit(_prompt(20, 9), 4, priority=0, seed=3, **GREEDY)
    eng.run_until_idle()
    assert eng.preemptions == 1 and low._preemptions == 1
    jobs = [(_prompt(20), 24, dict(GREEDY)), (_prompt(20, 9), 4, dict(GREEDY))]
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, [low, hi]) == 2
    assert not eng._inflight


# ---------------------------------------------------------------------------
# 4. handoff
# ---------------------------------------------------------------------------


def test_handoff_parks_only_fetched_pages(models):
    """A prefill_only request is exported after the tick that wrote its
    last page was fetched, with other rows decoding beside it; the pages
    serve the prompt on another engine like a local prefill."""
    ids = _prompt(5 * PAGE + 1, 9)
    sender = _engine(models)
    busy = sender.submit(_prompt(6, 1), 60, **GREEDY)
    for _ in range(4):
        sender.step()
    assert sender._inflight, "no tick in flight around the handoff"
    seen = []
    park = sender._handoff_ready_locked

    def parked(req, slot):
        seen.append([rec.no for rec in sender._inflight
                     if any(r is req for r, _, _ in rec.spans)])
        return park(req, slot)

    sender._handoff_ready_locked = parked
    blob, info = sender.prefill_and_export(ids)
    assert seen == [[]], "parked with a prompt chunk of its own in flight"
    assert info["pages"] == 5
    busy.result(timeout=60)

    receiver = _engine(models)
    assert receiver.import_kv(blob)["installed"] == 5
    req = receiver.submit(ids, 10, **GREEDY)
    receiver.run_until_idle()
    ref, _ = dense_greedy(models["cfg"], models["params"], ids, 10)
    assert req.result(timeout=60)[0] == ref
    assert receiver.prefix_hit_tokens == 5 * PAGE


# ---------------------------------------------------------------------------
# 5. lag 0 where the host cannot predict
# ---------------------------------------------------------------------------


def test_speculative_engine_applies_every_tick_at_once(models):
    jobs = [j for j in _backlog_jobs() if j[2].get("top_k") == 1][:5]
    before = _lag_counts()
    eng = _engine(models, spec_k=3, spec_draft=models["draft"],
                  spec_adaptive=False)
    ticks0 = eng.ticks
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    while not all(r.finished for r in reqs):
        eng.step()
        assert not eng._inflight
    lag = _lag_delta(before)
    assert lag == {"0": eng.ticks - ticks0, "1": 0}
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == 5


# ---------------------------------------------------------------------------
# 6. bookkeeping
# ---------------------------------------------------------------------------


def test_launch_of_the_next_tick_opens_before_the_fetch_of_this_one(models):
    old = obs_trace.get_tracer()
    tracer = obs_trace.configure(capacity=8192)
    try:
        eng = _engine(models)
        ticks0 = eng.ticks
        run_jobs(eng, _backlog_jobs()[:6])
        n = eng.ticks - ticks0
    finally:
        obs_trace._TRACER = old
    # events are (ph, name, ts, dur, ident, args) tuples
    at = {name: {e[5]["tick"]: e[2] for e in tracer.snapshot()
                 if e[0] == "X" and e[1] == name}
          for name in ("engine-launch", "engine-fetch", "engine-apply")}
    ticks = list(range(ticks0, ticks0 + n))
    for name in at:
        assert sorted(at[name]) == ticks, name
    for t in ticks:
        assert at["engine-launch"][t] < at["engine-fetch"][t] \
            < at["engine-apply"][t]
    ahead = [t for t in ticks[:-1]
             if at["engine-launch"][t + 1] < at["engine-fetch"][t]]
    assert len(ahead) >= 0.8 * n, (len(ahead), n)


def test_never_idle_with_a_tick_in_flight(models):
    eng = _engine(models)
    reqs = [eng.submit(p, n, **kw) for p, n, kw in _backlog_jobs()[:5]]
    in_flight_seen = 0
    for _ in range(10_000):
        n = eng.step()
        with eng._lock:
            in_flight = bool(eng._inflight)
            assert eng._idle_locked() == (
                not in_flight and not eng._queue
                and all(r is None for r in eng._slots))
        in_flight_seen += in_flight
        if n == 0:
            assert not in_flight
            break
    assert in_flight_seen and all(r.finished for r in reqs)
    # run_until_idle lands the last tick too
    more = [eng.submit(_prompt(9, i), 5, **GREEDY) for i in range(3)]
    eng.run_until_idle()
    assert all(r.finished for r in more) and not eng._inflight
    # and so does the loop's shutdown
    eng.submit(_prompt(9, 7), 30, **GREEDY)
    for _ in range(4):  # prefill, its landing, two decode launches
        eng.step()
    assert eng._inflight
    eng._land_inflight()
    assert not eng._inflight


def test_nothing_in_flight_after_idle_and_after_stop(models):
    """``run_until_idle`` and the loop's shutdown land the lagged tick:
    nothing is left launched, and ``mlt_engine_inflight_ticks`` says so."""
    gauge = obs_registry.get_registry().gauge("mlt_engine_inflight_ticks")
    eng = _engine(models)
    run_jobs(eng, _backlog_jobs()[:3])
    assert not eng._inflight and gauge.value == 0
    req, seen = eng.submit_stream(_prompt(9, 3), 100, **GREEDY)
    eng.start()
    assert seen.next_event(timeout=60) is not None   # a tick is ahead
    eng.stop()
    assert not req.finished and eng._thread is None
    assert not eng._inflight and gauge.value == 0


@pytest.mark.parametrize("seed", [0, 1, 42, 2 ** 31 - 1, 2 ** 31,
                                  2 ** 32 - 1, 2 ** 32 + 7, -5])
def test_request_key_is_prngkey_without_a_device_program(seed):
    """A request's sampling key is made on the host: ``PRNGKey`` as a device
    program would wait for the tick in flight, and hold the apply that
    activates the request with it (on the chip: 34 ms of apply where the
    parent's took 15)."""
    import numpy as np

    want = np.asarray(jax.random.PRNGKey(seed), np.uint32)
    got = engine_mod._request_key(seed)
    assert got.dtype == want.dtype and got.tolist() == want.tolist()


def test_ema_tick_reads_one_tick(models, monkeypatch):
    """Under the lag a launch-to-apply interval spans two ticks; the EMA
    that feeds Retry-After, shedding and the policies is fed the interval
    between two fetch completions.  A 50 ms sleep in the fetch stands for
    the device tick (the toy model's own is a millisecond or two); the
    launch-to-apply interval would read 100 ms and more."""
    tick_s = 0.05
    real_get = engine_mod.jax.device_get

    def slow_get(x):
        time.sleep(tick_s)
        return real_get(x)

    eng = _engine(models)
    eng.submit(_prompt(5), 30, **GREEDY)
    eng.step()
    eng.step()  # compiled, decoding
    monkeypatch.setattr(engine_mod.jax, "device_get", slow_get)
    eng.run_until_idle()
    monkeypatch.undo()
    ema = eng.scheduler_stats()["ema_tick_ms"] / 1e3
    assert tick_s <= ema < 1.8 * tick_s, ema
