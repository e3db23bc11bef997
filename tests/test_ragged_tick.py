"""Ragged paged attention tests (ISSUE 11).

Gates:

1. **Parity matrix** — what a request gets from the single-launch tick
   does not depend on what else the tick carries: greedy jobs emit the
   dense single-stream path's tokens and log-probs (the anchor), and
   every job, sampled ones too, the tokens and log-probs (within a few
   fp32 ulps) of the same engine serving it alone (tests/parity.py says
   why these two and not a second dispatch) across: decode-only,
   prefill-heavy, mixed, speculative (greedy and sampled), cache on/off,
   preemption/resume, and tp=4 (token identity).
2. **One launch per tick** — a mixed prefill+decode+spec tick dispatches
   exactly ONE compiled attention program, asserted via the engine's
   launch counter AND the ``engine-ragged-tick`` trace span (launches
   claimed in traces, not assumed).
3. **No recompiles** — tick-composition changes (different span/horizon
   mixes: all-decode, decode+prefill, multi-request prefill, drained)
   re-dispatch one executable (``_cache_size() == 1``).
4. **Token-level prefill budget** — ``SchedulerPolicy.prefill_budget`` is
   TOKENS: a budget of N admits multiple chunks from multiple requests
   into one tick; negative/typed-wrong budgets raise.
5. Telemetry: ``mlt_engine_tick_launches_total`` /
   ``mlt_engine_prefill_tokens_per_tick`` reach ``/metrics``.
6. **One dispatch** — the ragged tick is the only tick program an engine
   compiles (plus the scoring chunk of a ``return_log_probs`` prompt), and
   ``prefill_chunk`` is a positive whole number of pages wherever it
   enters.
"""

import numpy as np
import pytest

import jax

from megatron_llm_tpu.generation import ContinuousBatchingEngine, DraftModel
from megatron_llm_tpu.generation.scheduling import SchedulerPolicy

from tests.parity import (
    assert_greedy_match_dense,
    assert_logprobs_close,
    assert_same_generations,
    generations,
    run_jobs,
    serve_alone,
)

VOCAB = 67


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


def _mixed_jobs(n_new=10):
    """Short prompts (instant decode), long prompts (multi-chunk
    prefill), a shared prefix (cache/COW traffic), and sampled rows."""
    shared = [2 + (i * 7) % 60 for i in range(48)]  # 3 full pages @ 16
    jobs = []
    for i in range(3):
        jobs.append(([5 + i, 9, 2 + i], n_new,
                     dict(top_k=1, termination_id=10 ** 9)))
    for i in range(2):
        tail = [3 + (i * 11 + j) % 60 for j in range(60 + 13 * i)]
        jobs.append((shared + tail[:128 - len(shared) - n_new], n_new,
                     dict(top_k=1, termination_id=10 ** 9)))
    jobs.append((list(shared), 8, dict(top_k=1, termination_id=10 ** 9)))
    for i in range(2):
        p = [3 + (i * 5 + j) % 60 for j in range(40 + 11 * i)]
        jobs.append((p, n_new, dict(temperature=0.9, top_k=7,
                                    seed=42 + i, termination_id=10 ** 9)))
    return jobs


def _run(eng, jobs):
    return generations(run_jobs(eng, jobs))


def _assert_parity(models, jobs, n_greedy, **kw):
    """Both references of tests/parity.py for ``jobs`` served together by
    one engine built with ``kw``; the requests served together, and
    alone."""
    def make():
        return _engine(models, **kw)

    reqs = run_jobs(make(), jobs)
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, reqs) == n_greedy
    alone = serve_alone(make, jobs)
    assert_same_generations(generations(alone), generations(reqs),
                            "mixed vs alone")
    return reqs, alone


# ---------------------------------------------------------------------------
# 1. parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cache", [True, False])
def test_parity_mixed(models, cache):
    _assert_parity(models, _mixed_jobs(), 6, prefix_cache=cache)


def test_parity_decode_only(models):
    jobs = [([5, 9, 2 + i], 16, dict(top_k=1, termination_id=10 ** 9))
            for i in range(4)]
    _assert_parity(models, jobs, 4)


def test_parity_prefill_heavy(models):
    # prompts far longer than a chunk: most ticks are prefill-dominated
    jobs = [([2 + (i * 7 + j) % 60 for j in range(110 + 5 * i)], 6,
             dict(top_k=1, termination_id=10 ** 9)) for i in range(3)]
    _assert_parity(models, jobs, 3)


@pytest.mark.parametrize("cache", [True, False])
def test_parity_spec(models, cache):
    """Speculation is lossless: its greedy jobs are the dense greedy
    stream, its sampled jobs those of the same speculating engine serving
    the request alone."""
    _assert_parity(models, _mixed_jobs(), 6, spec_k=3,
                   spec_draft=models["draft"], spec_adaptive=False,
                   prefix_cache=cache)


def test_parity_spec_vs_nonspec_through_ragged(models):
    """The PR 9 losslessness contract survives the ragged rebuild:
    greedy spec rows through the ragged tick == plain ragged decode."""
    jobs = [j for j in _mixed_jobs() if "temperature" not in j[2]]
    plain = _run(_engine(models), jobs)
    spec = _run(_engine(models, spec_k=3,
                        spec_draft=models["draft"], spec_adaptive=False),
                jobs)
    assert_same_generations(plain, spec)


def test_parity_preemption_resume(models):
    """A mid-decode preemption + trie resume under the ragged tick is
    the uninterrupted stream, which is the dense greedy stream."""
    long = [2 + (j * 7) % 60 for j in range(48)]
    jobs = [(long, 14, dict(top_k=1, termination_id=10 ** 9)),
            ([5, 9, 2], 6, dict(top_k=1, termination_id=10 ** 9))]

    def run(preempt_at):
        eng = _engine(models, sched_policy="fcfs")
        req, other = [eng.submit(p, n, **kw) for p, n, kw in jobs]
        steps = 0
        while not req.finished:
            eng.step()
            steps += 1
            if steps == preempt_at and req._phase == "decode":
                assert eng.preempt(req)
        eng.run_until_idle()
        assert eng.preemptions == (preempt_at < 10 ** 9)
        return [req, other]

    base = run(10 ** 9)   # never preempted
    assert assert_greedy_match_dense(
        models["cfg"], models["params"], jobs, base) == 2
    for cut in (3, 6):
        assert_same_generations(generations(base), generations(run(cut)))


def test_parity_tp4_token_identity(models, eight_devices):
    from megatron_llm_tpu.core import parallel_state as ps
    from megatron_llm_tpu.models import init_model_params, make_config

    # tp=4 needs kv heads % 4 == 0 — a 4-kv-head sibling of the toy model
    cfg = make_config(
        "llama2", num_layers=2, hidden_size=64, num_attention_heads=4,
        num_attention_heads_kv=4, ffn_hidden_size=128, seq_length=256,
        max_position_embeddings=256, vocab_size=VOCAB,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype="float32", use_flash_attn=False)
    params = init_model_params(cfg, jax.random.PRNGKey(0))
    tpm = {"cfg": cfg, "params": params}

    jobs = _mixed_jobs(n_new=6)[:4]
    base = _run(_engine(tpm), jobs)
    mesh = ps.build_mesh(tensor_model_parallel_size=4,
                         data_parallel_size=1, devices=eight_devices[:4])
    tp = _run(_engine(tpm, mesh=mesh), jobs)
    for (t0, l0), (t1, l1) in zip(base, tp):
        assert t0 == t1  # tokens bitwise across tp
        np.testing.assert_allclose(l0, l1, atol=1e-5)


def test_parity_return_log_probs(models):
    """return_log_probs prompts take the teacher-forced scoring-chunk
    carve-out beside the tick: prompt AND generation log-probs are the
    dense scorer's, and those of the request served alone."""
    jobs = [([2 + (j * 7) % 60 for j in range(40)], 8,
             dict(top_k=1, termination_id=10 ** 9, return_log_probs=True)),
            ([5, 9, 2], 8, dict(top_k=1, termination_id=10 ** 9))]
    reqs, alone = _assert_parity(models, jobs, 2)
    assert reqs[1].prompt_log_probs is None
    assert len(reqs[0].prompt_log_probs) == 39
    assert_logprobs_close(alone[0].prompt_log_probs,
                          reqs[0].prompt_log_probs,
                          "teacher-forced prompt scores")


# ---------------------------------------------------------------------------
# 2 + 3. single launch per mixed tick; no recompiles across compositions
# ---------------------------------------------------------------------------


def test_mixed_tick_single_launch_and_span(models):
    """A tick carrying decode slots + a prefill chunk + spec-verify
    blocks is ONE launch — counter And trace span agree."""
    from megatron_llm_tpu.observability import trace as obs_trace

    old = obs_trace.get_tracer()
    tracer = obs_trace.configure(capacity=4096)
    try:
        eng = _engine(models, spec_k=2,
                      spec_draft=models["draft"], spec_adaptive=False)
        # saturate decode first
        short = [eng.submit([5 + i, 9, 2], 24, top_k=1,
                            termination_id=10 ** 9) for i in range(3)]
        for _ in range(4):
            eng.step()
        # now a long prompt arrives: the next steps mix prefill + decode
        long = eng.submit([2 + (j * 7) % 60 for j in range(90)], 4,
                          top_k=1, termination_id=10 ** 9)
        mixed_seen = False
        for _ in range(4):
            eng.step()
            decoding = sum(r is not None and r._phase == "decode"
                           for r in eng._slots)
            if long._phase == "prefill" and decoding:
                mixed_seen = True
                assert eng.last_tick_launches == 1, (
                    "mixed prefill+decode+spec tick dispatched more than "
                    "one attention program")
        assert mixed_seen, "workload never produced a mixed tick"
        eng.run_until_idle()
        for r in short + [long]:
            r.result(timeout=120)
    finally:
        obs_trace._TRACER = old

    # events are (ph, name, ts, dur, ident, args) tuples
    spans = [e for e in tracer.snapshot()
             if e[1] == "engine-ragged-tick"]
    assert spans, "no engine-ragged-tick spans recorded"
    mixed = [e for e in spans
             if (e[5] or {}).get("prefill_tokens", 0) > 0
             and (e[5] or {}).get("active", 0) > 0]
    assert mixed, "no mixed tick span recorded in traces"
    assert all((e[5] or {}).get("launches") == 1 for e in spans), (
        "a ragged-tick span claimed more than one launch")


def test_tick_phase_spans_and_kind_counters_agree(models):
    """ISSUE 24: every ragged tick is launch + fetch inside
    ``engine-ragged-tick``, between a plan and an apply; the launch span
    says which kind of tick it was, and the kind counters count the same
    ticks the spans show."""
    from megatron_llm_tpu.observability import registry as obs_registry
    from megatron_llm_tpu.observability import trace as obs_trace

    reg = obs_registry.get_registry()

    def kinds():
        return {k: reg.counter("mlt_engine_tick_kind_total",
                               labels={"kind": k}).value
                for k in ("decode", "prefill")}

    old = obs_trace.get_tracer()
    tracer = obs_trace.configure(capacity=8192)
    try:
        eng = _engine(models)
        before, ticks0 = kinds(), eng.ticks
        _run(eng, _mixed_jobs(n_new=6))
        after = kinds()
    finally:
        obs_trace._TRACER = old
    events = [e for e in tracer.snapshot() if e[0] == "X"]
    by = {}
    for e in events:
        by.setdefault(e[1], []).append(e)
    n = eng.ticks - ticks0
    assert n > 0
    for name in ("engine-ragged-tick", "engine-launch", "engine-fetch",
                 "engine-apply"):
        assert len(by[name]) == n, (name, len(by[name]), n)
    # an idle step plans and launches nothing: plans >= ticks, steps too
    assert len(by["engine-plan"]) >= n and len(by["engine-step"]) >= n
    assert len(by["engine-admit"]) == len(by["engine-step"])
    assert sorted({e[5]["tick"] for e in by["engine-step"]})[:n] == list(
        range(ticks0, ticks0 + n))
    launches = [e[5] for e in by["engine-launch"]]
    pre = [a for a in launches if a["prefill_rows"] > 0]
    dec = [a for a in launches if a["prefill_rows"] == 0]
    assert pre and dec, "the workload mixes prefill-carrying and decode ticks"
    assert all(a["prefill_rows"] % eng.prefill_chunk == 0
               and 0 < a["prefill_tokens"] <= a["prefill_rows"] for a in pre)
    assert all(a["prefill_tokens"] == 0 for a in dec)
    assert after["prefill"] - before["prefill"] == len(pre)
    assert after["decode"] - before["decode"] == len(dec)
    # the tick span no longer repeats the host-gap histogram (read by
    # nothing), and keeps the arguments the guides name
    assert all(set(e[5]) == {"active", "prefill_tokens", "launches", "k",
                             "tp"} for e in by["engine-ragged-tick"])


def test_composition_changes_reuse_bounded_executables(models):
    """The recompile-hazard gate: all-decode, mixed, multi-request
    prefill, spec depths, drained — every composition re-dispatches a
    BOUNDED executable set (one per bucketed live-prefill-row count, at
    most 1 + prefill_rows/prefill_chunk) and none of them ever
    re-traces: span/horizon/block-table metadata is data-carried, never
    static."""
    eng = _engine(models, spec_k=2,
                  spec_draft=models["draft"], spec_adaptive=False)
    _run(eng, _mixed_jobs())            # mixed compositions
    _run(eng, _mixed_jobs(n_new=4)[:2])  # different mix
    bound = 1 + eng.prefill_rows // eng.prefill_chunk
    assert eng._ragged_fns, "ragged tick never compiled"
    assert len(eng._ragged_fns) <= bound, (
        "tick-composition changes grew the executable set past the "
        "shape bound")
    for fn in eng._ragged_fns.values():
        assert fn._cache_size() == 1, (
            "a ragged executable re-traced on a composition change")

    eng2 = _engine(models)
    _run(eng2, _mixed_jobs())
    assert len(eng2._ragged_fns) <= 1 + (eng2.prefill_rows
                                         // eng2.prefill_chunk)
    for fn in eng2._ragged_fns.values():
        assert fn._cache_size() == 1


# ---------------------------------------------------------------------------
# 4. token-level prefill budget
# ---------------------------------------------------------------------------


class _TokenBudget(SchedulerPolicy):
    name = "_token_budget_test"
    barrier_admission = True

    def __init__(self, tokens, **kw):
        super().__init__(**kw)
        self.tokens = tokens

    def prefill_budget(self, prefilling, state):
        return self.tokens


def test_budget_admits_multiple_chunks_multiple_requests(models):
    """ISSUE 11 regression: prefill_budget is TOKENS — a 192-token budget
    packs 3 chunks spanning TWO requests into one tick."""
    eng = _engine(models, max_seq=256, sched_policy=_TokenBudget(192),
                  prefill_budget=192)
    r1 = eng.submit([2 + (j % 60) for j in range(150)], 4,
                    top_k=1, termination_id=10 ** 9)
    r2 = eng.submit([3 + (j % 60) for j in range(100)], 4,
                    top_k=1, termination_id=10 ** 9)
    eng.step()
    # r1's bucketed prompt (160) fills entirely; r2 gets the rest (32)
    assert r1._fill_pos == 160 and r2._fill_pos == 32, (
        r1._fill_pos, r2._fill_pos)
    assert eng.last_tick_launches == 1
    eng.run_until_idle()
    got = [r1.result(timeout=60), r2.result(timeout=60)]
    # aggressive packing is still bitwise the default pacing
    base = _run(_engine(models, max_seq=256),
                [([2 + (j % 60) for j in range(150)], 4,
                  dict(top_k=1, termination_id=10 ** 9)),
                 ([3 + (j % 60) for j in range(100)], 4,
                  dict(top_k=1, termination_id=10 ** 9))])
    assert_same_generations(base, got)


def test_budget_validated_as_tokens(models):
    """Negative or non-int budgets are policy bugs and raise."""
    eng = _engine(models, sched_policy=_TokenBudget(-1))
    eng.submit([2 + (j % 60) for j in range(80)], 2,
               top_k=1, termination_id=10 ** 9)
    with pytest.raises(ValueError, match="TOKENS"):
        eng.step()
    eng2 = _engine(models, sched_policy=_TokenBudget(2.5))
    eng2.submit([2 + (j % 60) for j in range(80)], 2,
                top_k=1, termination_id=10 ** 9)
    with pytest.raises(ValueError, match="TOKENS"):
        eng2.step()


def test_budget_floor_keeps_prefill_alive(models):
    """A zero budget still advances one chunk per tick (liveness — the
    legacy `max(1, ...)` guarantee, now in token units)."""
    eng = _engine(models, sched_policy=_TokenBudget(0))
    req = eng.submit([2 + (j % 60) for j in range(80)], 2,
                     top_k=1, termination_id=10 ** 9)
    eng.run_until_idle()
    req.result(timeout=60)


# ---------------------------------------------------------------------------
# 4b. the default pacing: capacity from the decode width, spent while
#     prompts wait (ISSUE 28)
# ---------------------------------------------------------------------------


def _wide(models, **kw):
    """A decode width of two chunks: the smallest engine whose default
    prefill capacity is more than one chunk."""
    return _engine(models, max_slots=32, prefill_chunk=16, **kw)


def _waiting_prompts():
    # page-bucketed to 48 + 64 + 32 + 64 = 208 rows: six ticks of two
    # chunks and one of one, so every bucket of the wide engine is used
    return [([2 + (i * 7 + j) % 60 for j in range(n)], 6,
             dict(top_k=1, termination_id=10 ** 9))
            for i, n in enumerate((40, 50, 30, 60))]


def _step_until_idle(eng, jobs):
    """The finished requests, and per step the prompt tokens prefilled and
    the programs launched."""
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    per_step = []
    while not all(r.finished for r in reqs):
        before = eng.prefill_tokens_computed
        eng.step()
        per_step.append((eng.prefill_tokens_computed - before,
                         eng.last_tick_launches))
    return reqs, per_step


def test_default_capacity_is_the_decode_width_in_chunks(models):
    """Nobody set a budget: the ragged tick's prompt-row capacity is
    max_slots in whole chunks, the default policy spends it while prompts
    wait, and the executable bound holds and is reached."""
    eng = _wide(models)
    assert eng.prefill_rows == eng.max_slots == 2 * eng.prefill_chunk
    _, per_step = _step_until_idle(eng, _waiting_prompts())
    prefilled = [n for n, _ in per_step if n]
    assert prefilled == [32] * 6 + [16], prefilled
    # every step launches one program but the last, which only lands the
    # tick in flight (the step runs one tick ahead of the host)
    assert all(launches == 1 for _, launches in per_step[:-1])
    assert per_step[-1] == (0, 0) and not eng._inflight
    assert sorted(eng._ragged_fns) == [0, 16, 32]
    assert len(eng._ragged_fns) == 1 + eng.prefill_rows // eng.prefill_chunk
    for fn in eng._ragged_fns.values():
        assert fn._cache_size() == 1
    # a capacity that is not a whole number of chunks rounds up
    assert _engine(models, max_slots=20, prefill_chunk=16).prefill_rows == 32


def _launch_sequence(eng, jobs):
    """(prefill rows, prompt tokens, decode rows) of every launch."""
    from megatron_llm_tpu.observability import trace as obs_trace

    old = obs_trace.get_tracer()
    tracer = obs_trace.configure(capacity=8192)
    try:
        out = _run(eng, jobs)
    finally:
        obs_trace._TRACER = old
    return out, [(e[5]["prefill_rows"], e[5]["prefill_tokens"],
                  e[5]["decode_rows"]) for e in tracer.snapshot()
                 if e[0] == "X" and e[1] == "engine-launch"]


@pytest.mark.parametrize("slots", [4, 64])
def test_narrow_engine_keeps_one_chunk_a_tick(models, slots):
    """max_slots <= prefill_chunk: the default is the explicit one-chunk
    budget, launch for launch."""
    default = _engine(models, max_slots=slots)
    pinned = _engine(models, max_slots=slots, prefill_budget=default.prefill_chunk)
    assert default.prefill_rows == pinned.prefill_rows == 64
    got, seq = _launch_sequence(default, _mixed_jobs(n_new=6))
    want, want_seq = _launch_sequence(pinned, _mixed_jobs(n_new=6))
    assert seq == want_seq
    assert any(rows for rows, _, _ in seq)
    assert_same_generations(want, got)


def test_default_pacing_is_lossless(models):
    """The wide default against an explicit one-chunk budget: the same
    tokens and log-probs (tests/parity.py's contract) in fewer ticks."""
    jobs = _waiting_prompts() + _mixed_jobs(n_new=6)
    packed = _wide(models)
    got, per_step = _step_until_idle(packed, jobs)
    paced = _wide(models, prefill_budget=16)
    want, paced_steps = _step_until_idle(paced, jobs)
    assert_same_generations(generations(want), generations(got))
    assert max(n for n, _ in paced_steps) == 16
    assert max(n for n, _ in per_step) == 32
    assert packed.ticks < paced.ticks


def test_scored_prompt_among_waiting_prompts_on_the_wide_engine(models):
    """The carve-out under the wide default pacing: a return_log_probs
    prompt waiting among plain prompts costs at most one scoring chunk
    beside the one tick program, a tick, and gets the tokens and prompt
    scores of the request served alone."""
    scored = ([2 + (j * 5) % 60 for j in range(50)], 6,
              dict(top_k=1, termination_id=10 ** 9, return_log_probs=True))
    jobs = _waiting_prompts()[:2] + [scored] + _waiting_prompts()[2:]
    eng = _wide(models)
    reqs, per_step = _step_until_idle(eng, jobs)
    launches = [n for _, n in per_step]
    # 50 tokens page-bucketed to 64 = four chunks of 16, one a tick, while
    # the tick beside it still packs two chunks of the waiting prompts
    assert max(launches) == 2 and launches.count(2) == 4, launches
    assert max(n for n, _ in per_step) == 48
    assert {rows for rows, _ in eng._chunk_fns} == {16}
    alone, = serve_alone(lambda: _wide(models), [scored])
    assert_same_generations(generations([alone]), generations([reqs[2]]))
    assert len(reqs[2].prompt_log_probs) == 49
    assert_logprobs_close(alone.prompt_log_probs, reqs[2].prompt_log_probs,
                          "teacher-forced prompt scores")


# ---------------------------------------------------------------------------
# 5. telemetry surface
# ---------------------------------------------------------------------------


def test_launch_metrics_on_scrape(models):
    from megatron_llm_tpu.observability import registry as obs_registry

    reg = obs_registry.get_registry()
    before = reg.counter("mlt_engine_tick_launches_total").value
    eng = _engine(models)
    _run(eng, _mixed_jobs(n_new=4)[:3])
    text = reg.render()
    assert "mlt_engine_tick_launches_total" in text
    assert "mlt_engine_prefill_tokens_per_tick" in text
    assert reg.counter("mlt_engine_tick_launches_total").value > before
    # launches == non-idle ticks
    assert eng.tick_launches == eng.ticks, (eng.tick_launches, eng.ticks)


# ---------------------------------------------------------------------------
# 6. one dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scored", [False, True],
                         ids=["plain", "with_log_probs"])
def test_engine_compiles_the_ragged_tick_and_nothing_else(models, scored):
    """After a mixed run an engine has compiled ``engine_ragged_tick``
    programs (and the page copy of copy-on-write) and no other tick or
    prefill program of its own; ``engine_prefill_chunk`` only when a
    ``return_log_probs`` prompt was served."""
    from megatron_llm_tpu.generation import generation as gen

    # a geometry no other test builds: whatever this engine compiles is
    # new to the process-wide program cache
    eng = _engine(models, max_slots=5, num_pages=83 + scored)
    jobs = _mixed_jobs(n_new=4)
    if scored:
        jobs[3][2]["return_log_probs"] = True
    before = set(gen._JIT_CACHE)
    reqs = run_jobs(eng, jobs)
    names = sorted({k[1] for k in set(gen._JIT_CACHE) - before})
    want = ["engine_copy_page"] + (["engine_prefill_chunk"] if scored
                                   else []) + ["engine_ragged_tick"]
    assert names == want, names
    assert eng.cow_copies >= 1 and len(eng._ragged_fns) == 2
    assert bool(eng._chunk_fns) == scored
    assert (reqs[3].prompt_log_probs is not None) == scored


@pytest.mark.parametrize("chunk", [0, 24, -16])
def test_prefill_chunk_must_be_whole_pages_constructor(models, chunk):
    with pytest.raises(ValueError,
                       match="positive whole number of pages"):
        _engine(models, prefill_chunk=chunk)


def test_prefill_chunk_must_be_whole_pages_server_arguments(monkeypatch):
    """The server's argument parsing refuses the same values with the
    same message, before it builds anything."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "run_text_generation_server",
        Path(__file__).parent.parent / "tools"
        / "run_text_generation_server.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    for chunk in ("0", "24"):
        monkeypatch.setattr("sys.argv", [
            "run_text_generation_server.py", "--random_init",
            "--tokenizer_type", "NullTokenizer", "--vocab_size", "128",
            "--prefill_chunk", chunk])
        with pytest.raises(ValueError,
                           match="positive whole number of pages"):
            tool.main()
