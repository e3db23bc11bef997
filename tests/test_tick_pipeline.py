"""Pipelined multi-tick dispatch tests (ISSUE 17).

Gates:

1. **Parity matrix** — ``--tick_pipeline_depth`` ∈ {1, 2, 3} emits
   tokens AND log-probs bitwise-identical to depth 0 (today's
   one-tick-per-launch driver) across: greedy and sampled rows, prefix
   cache on/off, every stop mode (termination id, EOL, double-EOL)
   actually FIRING on device, mixed admission/prefill boundaries,
   preemption/resume mid-pipeline, and contention under the priority
   and slo scheduling policies.
2. **Lag-boundary correctness** — a preemption landing while a chain is
   in flight discards the overrun ticks and the victim's resume replays
   them bitwise (the ``fold_in(key, step)`` stream); stop tokens and
   token budgets detected in-program freeze the row exactly where the
   host's apply rules would.
3. **Ledger safety** — pre-granted page budgets (``_pregrant_locked``)
   never fail an in-flight alloc on a tight pool, and every page comes
   back after drain (no leaks vs the depth-0 run).
4. **Degradation** — speculative engines ignore the flag (depth-0 per
   tick acceptance) and depth 0 itself never touches pipeline state.
5. **Telemetry** — ``engine-chained-tick`` spans carry chain/host-gap
   attrs, the in-flight gauge returns to 0 at drain, and chains
   measurably reduce host dispatch count.
6. **graftcheck** — the chained builder's traced bodies are in the
   sync-in-jit analyzed set (builder-factory convention), and a
   builder factory hiding a host sync is flagged.
"""

import os
import sys

import numpy as np
import pytest

import jax

from megatron_llm_tpu.generation import ContinuousBatchingEngine, DraftModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# big enough that the GPT-2 EOL (198) / double-EOL (628) ids are real
# outputs — the device-side stop modes must actually fire, not idle
VOCAB = 700


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


def _engine(models, depth, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(models["cfg"], models["params"], None,
                                    tick_pipeline_depth=depth, **kw)


def _run(eng, jobs):
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.run_until_idle()
    return [r.result(timeout=120) for r in reqs]


def _assert_bitwise(a, b, what="pipelined"):
    assert len(a) == len(b)
    for k, ((t0, l0), (t1, l1)) in enumerate(zip(a, b)):
        assert t0 == t1, f"row {k}: {what} tokens diverged from depth 0"
        assert l0 == l1, f"row {k}: {what} log-prob bits diverged"


def _steady_jobs(n_new=14):
    """Greedy + sampled rows, every stop mode armed, budgets that expire
    mid-chain (not multiples of any depth), a shared prefix (cache/COW
    traffic) and a long prompt (admission/prefill boundary mid-run)."""
    shared = [2 + (i * 7) % 60 for i in range(48)]  # 3 full pages @ 16
    return [
        ([5, 9, 2], n_new, dict(top_k=1, termination_id=10 ** 9)),
        ([7, 3], 11, dict(temperature=0.9, top_k=7, seed=42,
                          termination_id=10 ** 9)),
        ([11, 4, 6], n_new + 3, dict(top_k=1, stop_on_eol=True)),
        ([9, 9, 1], n_new + 3, dict(top_k=1, stop_on_double_eol=True)),
        (list(shared), 9, dict(top_k=1, termination_id=10 ** 9)),
        (shared + [3, 4, 5], 9, dict(top_k=1, termination_id=10 ** 9)),
        ([6, 1], 7, dict(temperature=1.1, top_k=0, top_p=0.9, seed=7,
                         termination_id=10 ** 9)),
    ]


# ---------------------------------------------------------------------------
# 1. parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cache", [True, False])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_parity_matrix(models, cache, depth):
    base = _run(_engine(models, 0, prefix_cache=cache), _steady_jobs())
    got = _run(_engine(models, depth, prefix_cache=cache), _steady_jobs())
    _assert_bitwise(base, got, f"depth {depth}")


def test_parity_termination_fires_mid_chain(models):
    """The device-side termination-id detector stops a row exactly where
    the host would: pick the id off the depth-0 greedy stream so the
    stop genuinely fires inside a chain, with a second row decoding
    past it (freeze must not perturb the survivor)."""
    probe = _run(_engine(models, 0),
                 [([5, 9, 2], 30, dict(top_k=1, termination_id=10 ** 9))])
    term = probe[0][0][3:][7]
    jobs = [([5, 9, 2], 30, dict(top_k=1, termination_id=term)),
            ([7, 3], 30, dict(top_k=1, termination_id=10 ** 9))]
    base = _run(_engine(models, 0), jobs)
    assert base[0][0][-1] == term, "probe id never fired — dead test"
    for depth in (1, 2, 3):
        _assert_bitwise(base, _run(_engine(models, depth), jobs),
                        f"depth {depth} termination")


def test_parity_eol_stop_modes_fire(models):
    """EOL / double-EOL stop modes run in-program: find a sampled stream
    that really emits EOL (198), then check stop_on_eol halts on it and
    stop_on_double_eol correctly does NOT halt on a single EOL —
    bitwise against depth 0 either way."""
    hit = None
    for seed in range(30):
        eng = _engine(models, 0)
        r = eng.submit([5, 9, 2], 40, temperature=1.3, top_k=0,
                       seed=seed, termination_id=10 ** 9)
        eng.run_until_idle()
        if 198 in r.result(timeout=60)[0][3:]:
            hit = seed
            break
    assert hit is not None, "no sampled stream emitted EOL — dead test"
    jobs = [([5, 9, 2], 40, dict(temperature=1.3, top_k=0, seed=hit,
                                 stop_on_eol=True)),
            ([5, 9, 2], 40, dict(temperature=1.3, top_k=0, seed=hit,
                                 stop_on_double_eol=True))]
    base = _run(_engine(models, 0), jobs)
    assert base[0][0][-1] in (198, 628), "EOL mode never stopped"
    assert len(base[1][0]) >= len(base[0][0]), (
        "double-EOL mode stopped no later than single-EOL — suspicious")
    for depth in (1, 2):
        _assert_bitwise(base, _run(_engine(models, depth), jobs),
                        f"depth {depth} eol")


def test_parity_preempt_mid_pipeline(models):
    """Force-preempt a decoding request between pipelined steps — with a
    chain in flight, the overrun ticks are discarded and the resume
    replays them bitwise (fold_in(key, step) stream)."""
    def run(depth, preempt_at):
        eng = _engine(models, depth, sched_policy="fcfs")
        long = [2 + (j * 7) % 60 for j in range(48)]
        req = eng.submit(long, 14, top_k=1, termination_id=10 ** 9)
        other = eng.submit([5, 9, 2], 6, top_k=1, termination_id=10 ** 9)
        steps = preempted_in_flight = 0
        while not req.finished:
            eng.step()
            steps += 1
            if steps == preempt_at and req._phase == "decode":
                if depth and eng._inflight:
                    preempted_in_flight = 1
                assert eng.preempt(req)
        eng.run_until_idle()
        return ([req.result(timeout=120), other.result(timeout=120)],
                preempted_in_flight)

    from tests.parity import assert_same_generations

    base, _ = run(0, 10 ** 9)  # never preempted
    in_flight_seen = 0
    for depth in (0, 1, 2):
        for cut in (3, 5):
            got, inflight = run(depth, cut)
            # tokens bit for bit; log-probs to the ulps of tests/parity.py:
            # the resume's re-prefill puts the OTHER request's row into a
            # prompt-carrying tick, another program than the base run's
            # decode-only tick for the same token (which token that is
            # follows the preemption's tick, so bit equality was a
            # coincidence of the cut)
            assert_same_generations(base, got,
                                    f"depth {depth} preempt@{cut}")
            in_flight_seen += inflight
    assert in_flight_seen, (
        "no preemption ever landed with a chain in flight — the lag "
        "boundary was never exercised")


@pytest.mark.parametrize("policy", ["priority", "slo"])
def test_parity_under_contention_policies(models, policy):
    """Admission-time scheduler decisions (priority order, EDF) are
    boundary work — depth 2 under slot contention stays bitwise."""
    def jobs():
        out = []
        for i in range(5):
            kw = dict(top_k=1, termination_id=10 ** 9)
            if policy == "priority":
                kw["priority"] = i % 3
            else:
                kw["ttft_deadline_ms"] = 60_000 + 10_000 * i
            out.append(([5 + i, 9, 2 + i], 10 + i, kw))
        return out

    base = _run(_engine(models, 0, max_slots=2, sched_policy=policy),
                jobs())
    got = _run(_engine(models, 2, max_slots=2, sched_policy=policy),
               jobs())
    _assert_bitwise(base, got, f"{policy} depth 2")


# ---------------------------------------------------------------------------
# 3 + 4. ledger safety on a tight pool; degradation rules
# ---------------------------------------------------------------------------


def test_ledger_safety_tight_pool(models):
    """Pre-granted budgets draw pages EARLY (up to 2·depth positions
    ahead) but never more than admission committed: on a pool sized to
    the bone, no in-flight alloc fails, results stay bitwise, and every
    page returns to the free list at drain."""
    kw = dict(max_slots=4, page_size=16, num_pages=40, prefix_cache=False)
    jobs = [([5 + i, 9, 2 + i], 40, dict(top_k=1, termination_id=10 ** 9))
            for i in range(4)]
    eng0 = _engine(models, 0, **kw)
    base = _run(eng0, jobs)
    eng2 = _engine(models, 2, **kw)
    got = _run(eng2, jobs)
    _assert_bitwise(base, got, "tight-pool depth 2")
    assert eng2.pool.num_free == eng0.pool.num_free, "pages leaked"
    assert not eng2._inflight and eng2._pipe_state is None


def test_spec_engines_degrade_to_depth0(models):
    """Speculative decoding needs per-tick acceptance on the host — the
    flag is ignored (never chains) and results are bitwise the spec
    depth-0 run."""
    kw = dict(spec_k=3, spec_draft=models["draft"], spec_adaptive=False)
    jobs = [j for j in _steady_jobs() if "temperature" not in j[2]]
    base = _run(_engine(models, 0, **kw), jobs)
    eng = _engine(models, 2, **kw)
    got = _run(eng, jobs)
    _assert_bitwise(base, got, "spec depth 2")
    assert eng._chained_fn is None, "spec engine built the chained tick"
    assert not eng._inflight and eng._pipe_state is None


def test_depth0_never_touches_pipeline_state(models):
    """Depth 0 is the seed driver byte for byte: no chain program, no
    in-flight state — only the (new, always-on) host-gap bookkeeping."""
    eng = _engine(models, 0)
    _run(eng, _steady_jobs()[:3])
    assert eng.pipeline_depth == 0
    assert eng._chained_fn is None
    assert not eng._inflight and eng._pipe_state is None
    stats = eng.host_gap_stats()
    assert stats["count"] > 0 and stats["p50_ms"] <= stats["p99_ms"]


# ---------------------------------------------------------------------------
# 5. telemetry: spans, gauges, measurably fewer host dispatches
# ---------------------------------------------------------------------------


def test_chained_span_and_inflight_gauge(models):
    from megatron_llm_tpu.observability import registry as obs_registry
    from megatron_llm_tpu.observability import trace as obs_trace

    old = obs_trace.get_tracer()
    tracer = obs_trace.configure(capacity=4096)
    try:
        eng = _engine(models, 2)
        _run(eng, _steady_jobs()[:4])
    finally:
        obs_trace._TRACER = old
    # events are (ph, name, ts, dur, ident, args) tuples
    spans = [e for e in tracer.snapshot()
             if e[1] == "engine-chained-tick"]
    assert spans, "no chained-tick spans recorded"
    assert all((e[5] or {}).get("chain") == 2 for e in spans)
    gaps = [(e[5] or {}).get("host_gap_ms") for e in spans]
    assert any(g is not None and g >= 0 for g in gaps), (
        "no chained span carried a host-gap attr")
    reg = obs_registry.get_registry()
    text = reg.render()
    assert "mlt_engine_host_gap_seconds" in text
    assert "mlt_engine_inflight_ticks" in text
    assert "mlt_engine_tick_pipeline_depth" in text
    assert reg.gauge("mlt_engine_inflight_ticks").value == 0, (
        "in-flight gauge did not return to 0 at drain")


def test_chaining_reduces_host_dispatches(models):
    """The point of the PR: N-tick chains mean ~N× fewer host dispatch
    boundaries for the same token stream."""
    jobs = [([5 + i, 9, 2], 24, dict(top_k=1, termination_id=10 ** 9))
            for i in range(4)]
    count0 = _engineed_dispatches(models, 0, jobs)
    count2 = _engineed_dispatches(models, 2, jobs)
    assert count2 < count0 * 0.7, (count0, count2)


def _engineed_dispatches(models, depth, jobs):
    eng = _engine(models, depth)
    _run(eng, jobs)
    return eng.host_gap_stats()["count"]


# ---------------------------------------------------------------------------
# 6. graftcheck: the chained builder is analyzed; bad builders flag
# ---------------------------------------------------------------------------


def test_chained_builder_in_traced_set():
    """The builder-factory convention (module-level ``make_*_fn``)
    reaches the ragged/chained tick bodies the per-file resolver cannot
    — the compiled chain really is sync-analyzed."""
    from tools.graftcheck import core
    from tools.graftcheck.rules.sync import SyncInJitRule

    path = os.path.join(REPO, "megatron_llm_tpu", "generation",
                        "ragged.py")
    ctx = core.FileContext(path)
    names = {getattr(n, "name", "<lambda>")
             for n in SyncInJitRule()._traced_nodes(ctx)}
    assert {"chained", "body", "target_forward"} <= names, names
    # the factory body itself runs at build time (host side) — exempt
    assert "make_chained_tick_fn" not in names


def test_builder_factory_sync_flagged():
    """A chained builder hiding a host sync inside the compiled body is
    a finding; a jax-free host-side factory (REST client shape) is not
    traced at all."""
    from tools.graftcheck import core
    from tools.graftcheck.rules import ALL_RULES as _RULES

    bad = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def make_bad_tick_fn(cfg):\n"
        "    def tick(x):\n"
        "        return np.asarray(x) + jnp.ones(())\n"
        "    return tick\n"
    )
    hits = [f for f in core.check_file("fixture.py", _RULES, source=bad)
            if f.rule == "sync-in-jit"]
    assert len(hits) == 1 and hits[0].line == 6, hits
    host = (
        "import requests\n"
        "def make_api_generate_fn(url):\n"
        "    def fn(text):\n"
        "        return float(requests.get(url).elapsed.total_seconds())\n"
        "    return fn\n"
    )
    assert not [f for f in core.check_file("fixture.py", _RULES, source=host)
                if f.rule == "sync-in-jit"]
