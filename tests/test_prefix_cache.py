"""Prefix-cached paged KV + chunked prefill tests (ISSUE 5).

Gates: (1) generation is identical (tokens, and log-probs to a few ulps,
jnp fallback) with the prefix cache on vs off, across chunk sizes, and
against the dense single-stream path — sharing pages and splitting
prompts must be pure optimizations;
(2) page refcounts are exact under alloc/share/release/evict churn: no
page is ever simultaneously free and referenced, copy-on-write never
mutates a shared page, and the pool drains whole; (3) admission under page
pressure evicts cached-idle pages (LRU, leaf-first) instead of rejecting
while reusable pages sit idle; (4) prefill chunks interleave with decode
ticks instead of stalling active slots.
"""

import numpy as np
import pytest

import jax

from megatron_llm_tpu.generation import (
    ContinuousBatchingEngine,
    EngineOverloaded,
)
from megatron_llm_tpu.generation.pools import (
    NULL_PAGE,
    PagedKVPool,
    PrefixCache,
)
from megatron_llm_tpu.models import init_model_params, make_config

from tests.parity import (
    DENSE_ATOL,
    assert_greedy_match_dense,
    assert_logprobs_close,
    assert_same_generations,
    dense_greedy,
    generations,
    run_jobs,
)

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


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    return ContinuousBatchingEngine(cfg, params, ToyTokenizer(), **kw)


def _run(eng, jobs):
    """Submit (prompt, max_new, kwargs) jobs sequentially-admitted but
    batch-decoded; returns [(tokens, gen_log_probs, prompt_log_probs)]."""
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.run_until_idle()
    out = []
    for r in reqs:
        toks, lps = r.result(timeout=30)
        out.append((toks, lps, r.prompt_log_probs))
    return out


SHARED = [2 + (i * 7) % 60 for i in range(48)]  # 3 full pages @ page 16


# ---------------------------------------------------------------------------
# Bitwise parity
# ---------------------------------------------------------------------------


def test_bitwise_parity_cache_on_vs_off(toy_model):
    """Same traffic through cache-on and cache-off engines: identical
    tokens, log-probs to a few fp32 ulps (tests/parity.py) — shared pages
    must hold the KV a cold prefill would compute."""
    cfg, params = toy_model
    jobs = []
    for i in range(6):
        tail = [3 + (i * 11 + j) % 60 for j in range(5 + 3 * i)]
        jobs.append((SHARED + tail, 10,
                     dict(top_k=1, termination_id=10 ** 9)))
    # one page-aligned full duplicate (the COW path) and one sampled row
    jobs.append((list(SHARED), 8, dict(top_k=1, termination_id=10 ** 9)))
    jobs.append((list(SHARED), 8, dict(top_k=1, termination_id=10 ** 9)))
    jobs.append((SHARED + [5, 6], 8,
                 dict(temperature=0.8, top_p=0.9, seed=7,
                      termination_id=10 ** 9)))

    # submit one-by-one so later requests can hit what earlier ones cached
    on = _engine(cfg, params, prefix_cache=True)
    res_on = []
    for j in jobs:
        res_on.extend(_run(on, [j]))
    off = _engine(cfg, params, prefix_cache=False)
    res_off = []
    for j in jobs:
        res_off.extend(_run(off, [j]))

    for (t1, lp1, _), (t2, lp2, _) in zip(res_on, res_off):
        assert t1 == t2
        assert_logprobs_close(lp1, lp2)
    assert on.prefix_hit_tokens > 0, "shared prefix never hit the cache"
    assert off.prefix_hit_tokens == 0
    assert on.prefill_tokens_computed < off.prefill_tokens_computed
    assert on.cow_copies >= 1, "page-aligned duplicate must take COW path"


def test_bitwise_parity_chunked_vs_monolithic(toy_model):
    """Chunked prefill (cache off) == the dense single-stream path, which
    prefills nothing in chunks, across chunk sizes and prompt lengths that
    straddle chunk/bucket boundaries; the sampled job, which the dense
    sampler does not draw, is the same at every chunk size.  (The name
    keeps the node id; the unchunked engine it once compared with is
    gone.)"""
    cfg, params = toy_model
    prompts = [
        [2 + (j * 5) % 60 for j in range(n)] for n in (3, 16, 40, 64, 90)
    ]
    jobs = [(p, 12, dict(top_k=1, termination_id=10 ** 9)) for p in prompts]
    jobs.append((prompts[2], 12,
                 dict(temperature=0.7, top_p=0.8, seed=3,
                      termination_id=10 ** 9)))

    first = None
    for chunk in (16, 32, 64):
        ch = _engine(cfg, params, prefix_cache=False, prefill_chunk=chunk)
        reqs = run_jobs(ch, jobs)
        assert assert_greedy_match_dense(cfg, params, jobs, reqs) == 5
        if first is None:
            first = generations(reqs)
        assert_same_generations(first, generations(reqs),
                                f"chunk={chunk} vs chunk=16")


def test_log_prob_requests_skip_match_but_feed_cache(toy_model):
    """return_log_probs recomputes the whole prompt (chunked teacher-forced
    scores match the dense scorer's) and still caches its pages
    for later non-scoring requests."""
    cfg, params = toy_model
    prompt = SHARED[:40]

    _, ref_lp = dense_greedy(cfg, params, prompt, 6)
    eng = _engine(cfg, params, prefix_cache=True)
    (_, _, plp_ch), = _run(
        eng, [(prompt, 6, dict(top_k=1, termination_id=10 ** 9,
                               return_log_probs=True))])
    # chunk-accumulated scores
    np.testing.assert_allclose(plp_ch, ref_lp[:len(prompt) - 1], rtol=0,
                               atol=DENSE_ATOL)
    assert eng.prefix_hit_tokens == 0
    # the scoring request's pages are now reusable
    (_, _, _), = _run(eng, [(prompt, 6, dict(top_k=1,
                                             termination_id=10 ** 9))])
    assert eng.prefix_hit_tokens > 0


# ---------------------------------------------------------------------------
# COW and refcount invariants
# ---------------------------------------------------------------------------


def _assert_page_states(eng):
    """Every page is free XOR referenced XOR cached-idle; refcounts equal
    the number of block tables holding the page."""
    from collections import Counter

    pool = eng.pool
    holders = Counter(p for r in eng._slots if r is not None
                      for p in r._mem[0].pages)
    free = set(pool._free)
    assert NULL_PAGE not in free and holders.get(NULL_PAGE, 0) == 0
    for p in range(1, pool.num_pages):
        assert pool.refcounts[p] == holders.get(p, 0), \
            f"page {p}: refcount {pool.refcounts[p]} != holders {holders.get(p, 0)}"
        if p in free:
            assert pool.refcounts[p] == 0 and p not in pool.cached, \
                f"page {p} both free and referenced/cached"
    cached_idle = sum(1 for p in pool.cached if pool.refcounts[p] == 0)
    assert len(holders) + pool.num_free + cached_idle == pool.num_pages - 1


def test_cow_never_mutates_shared_page(toy_model):
    """A page-aligned fully-cached prompt re-admission copies the last
    shared page before the refeed tick writes it: the cached page's bytes
    are unchanged afterwards, and the copy produced identical output."""
    cfg, params = toy_model
    eng = _engine(cfg, params, prefix_cache=True)
    # cache pages 0..2 (positions 0..47) from a 53-token prompt
    (_, _, _), = _run(eng, [(SHARED + [5, 6, 7, 8, 9], 6,
                             dict(top_k=1, termination_id=10 ** 9))])
    cached_pages = sorted(eng.pool.cached)
    assert len(cached_pages) == 3
    before = {p: np.asarray(eng.pool.kv[:, p]).copy() for p in cached_pages}

    # a page-aligned PREFIX of the cached prompt is fully covered: its
    # refeed tick would write the last shared page -> COW
    prompt = list(SHARED[:48])
    baseline = _engine(cfg, params, prefix_cache=False)
    (t1, _, _), = _run(baseline, [(prompt, 6, dict(top_k=1,
                                                   termination_id=10 ** 9))])
    (t2, _, _), = _run(eng, [(prompt, 6, dict(top_k=1,
                                              termination_id=10 ** 9))])
    assert eng.cow_copies == 1
    assert eng.prefill_tokens_computed > 0  # only the first prompt's chunks
    assert t2 == t1  # identical greedy continuation off the copied page
    for p in cached_pages:
        np.testing.assert_array_equal(
            before[p], np.asarray(eng.pool.kv[:, p]),
            err_msg=f"shared page {p} mutated")
    _assert_page_states(eng)


def test_refcount_invariants_under_shared_stress(toy_model):
    """Churn shared-prefix traffic through a tight pool: refcounts stay
    exact at every step, shared pages are held by several block tables at
    once, and the pool drains whole (free + cached-idle)."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=3, page_size=16, num_pages=17,
                  prefix_cache=True)
    rng = np.random.default_rng(1)
    families = [SHARED[:32], [9 + (j * 3) % 50 for j in range(32)]]
    reqs = []
    for i in range(14):
        fam = families[int(rng.integers(0, 2))]
        tail = [2 + int(x) for x in rng.integers(0, 60,
                                                 int(rng.integers(0, 12)))]
        plen_extra = int(rng.integers(1, 20))
        reqs.append(eng.submit(list(fam) + tail, plen_extra, top_k=1,
                               termination_id=10 ** 9))
    steps = 0
    saw_sharing = False
    while True:
        n = eng.step()
        steps += 1
        _assert_page_states(eng)
        from collections import Counter

        holders = Counter(p for r in eng._slots if r is not None
                          for p in r._mem[0].pages)
        if any(c > 1 for c in holders.values()):
            saw_sharing = True
        if n == 0 and not eng._queue:
            break
        assert steps < 5000
    assert saw_sharing, "stress never exercised page sharing"
    for r in reqs:
        toks, _ = r.result(timeout=5)
        assert 1 <= len(r.generated) <= r.max_new_tokens
    assert int(eng.pool.refcounts.sum()) == 0
    assert eng.pool.num_free + len(eng.pool.cached) == eng.pool.num_pages - 1


# ---------------------------------------------------------------------------
# Eviction and admission under pressure
# ---------------------------------------------------------------------------


def test_eviction_under_pressure_admits_instead_of_starving(toy_model):
    """With most pages parked in the cache, a request whose worst case
    exceeds the FREE list must still admit by evicting cached-idle pages —
    pool exhaustion no longer means waiting while reusable pages sit
    idle."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, page_size=16, num_pages=10,
                  prefix_cache=True)
    # park 3 pages in the cache (prompt 64 -> (64-1)//16 = 3 cacheable)
    prompt64 = [2 + (j * 7) % 60 for j in range(64)]
    _run(eng, [(prompt64, 4, dict(top_k=1, termination_id=10 ** 9))])
    assert len(eng.pool.cached) == 3
    parked = set(eng.pool.cached)
    free_before = eng.pool.num_free
    # worst case 7 pages > free list, but free + evictable covers it
    need = -(-(80 + 30) // eng.page_size)
    assert need > free_before
    prompt = [11 + (j * 13) % 50 for j in range(80)]
    (toks, _, _), = _run(eng, [(prompt, 30, dict(top_k=1,
                                                 termination_id=10 ** 9))])
    assert len(toks) == 110
    assert len(eng.pool.cached & parked) < 3, "nothing was evicted"
    _assert_page_states(eng)


HOST_PHASES = {"dispatch": ("admit", "plan", "launch"), "apply": ("apply",)}


def _engine_counters():
    from megatron_llm_tpu.observability import registry as registry_mod

    reg = registry_mod.get_registry()
    out = {"ticks": reg.counter("mlt_engine_ticks_total").value,
           "dry": reg.counter("mlt_engine_pool_dry_ticks_total").value,
           "evicted": reg.counter(
               "mlt_engine_prefix_evicted_pages_total").value,
           "by_evict": reg.counter("mlt_engine_pool_alloc_pages_total",
                                   labels={"source": "evict"}).value}
    for ph in ("admit", "plan", "launch", "fetch", "apply"):
        _, out[ph], out[ph + "_n"] = reg.histogram(
            "mlt_engine_tick_phase_seconds", labels={"phase": ph}).snapshot()
    for side in HOST_PHASES:
        _, out["cpu_" + side], out["cpu_" + side + "_n"] = reg.histogram(
            "mlt_engine_tick_host_cpu_seconds",
            labels={"side": side}).snapshot()
    return out


def test_dry_tick_is_counted_by_the_step_and_the_flag_cleared(toy_model):
    """A tick during whose admit or plan a grant had to evict is counted
    dry, once, by the step that launches it; the pool's flag is the
    step's to clear; a run that evicts nothing counts none."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, page_size=16, num_pages=10,
                  prefix_cache=True)
    c0 = _engine_counters()
    prompt64 = [2 + (j * 7) % 60 for j in range(64)]
    _run(eng, [(prompt64, 4, dict(top_k=1, termination_id=10 ** 9))])
    c1 = _engine_counters()
    assert c1["ticks"] > c0["ticks"] and c1["dry"] == c0["dry"]
    assert c1["evicted"] == c0["evicted"] and not eng.pool.reclaimed
    prompt = [11 + (j * 13) % 50 for j in range(80)]
    _run(eng, [(prompt, 30, dict(top_k=1, termination_id=10 ** 9))])
    c2 = _engine_counters()
    evicted = c2["evicted"] - c1["evicted"]
    assert evicted >= 1 and c2["by_evict"] - c1["by_evict"] == evicted
    dry = c2["dry"] - c1["dry"]
    # at least the tick that admitted the request; never more than a tick
    # a victim, never more than the ticks run
    assert 1 <= dry <= min(evicted, c2["ticks"] - c1["ticks"])
    assert not eng.pool.reclaimed, "the launching step clears the flag"
    _assert_page_states(eng)


def test_host_cpu_is_the_scheduler_threads_own(toy_model, monkeypatch):
    """mlt_engine_tick_host_cpu_seconds reads the driving thread's CPU
    clock: a side spent asleep reads near 0 however busy another thread
    is meanwhile, and no side's CPU exceeds the wall time of its phases."""
    import threading
    import time

    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, page_size=16, num_pages=16)
    _run(eng, [([5, 6, 7, 8], 3, dict(top_k=1, termination_id=10 ** 9))])
    admit = eng._admit

    def sleepy_admit():
        time.sleep(0.05)                  # off the CPU, inside dispatch
        admit()

    monkeypatch.setattr(eng, "_admit", sleepy_admit)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(2000))

    other = threading.Thread(target=spin, daemon=True)
    other.start()
    c0 = _engine_counters()
    try:
        _run(eng, [([9, 10, 11, 12], 6,
                    dict(top_k=1, termination_id=10 ** 9))])
    finally:
        stop.set()
        other.join(timeout=30)
    assert not other.is_alive()
    c1 = _engine_counters()
    d = {k: c1[k] - c0[k] for k in c1}
    n = d["admit_n"]
    assert n >= 5 and d["cpu_dispatch_n"] == n
    wall = {side: sum(d[ph] for ph in phases)
            for side, phases in HOST_PHASES.items()}
    assert wall["dispatch"] >= 0.05 * n
    assert d["cpu_dispatch"] < 0.2 * wall["dispatch"], d
    for side in HOST_PHASES:
        assert d["cpu_" + side + "_n"] == d["apply_n"] > 0
        # thread_time ticks coarser than the wall clock: a tick of room
        assert d["cpu_" + side] <= wall[side] + 0.011 * n, (side, d)


def test_four_cpu_clock_reads_a_tick(toy_model, monkeypatch):
    """time.thread_time is a system call (5.6 to 23.8 us on the chip's
    host): a launched tick reads it at most four times, the start of
    admit, the end of launch, and the two ends of apply."""
    import time
    import types

    from megatron_llm_tpu.generation import engine as engine_mod

    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, page_size=16, num_pages=16)
    reads = []

    def counting_thread_time():
        reads.append(1)
        return time.thread_time()

    clock = types.SimpleNamespace(**{
        k: getattr(time, k) for k in dir(time) if not k.startswith("_")})
    clock.thread_time = counting_thread_time
    monkeypatch.setattr(engine_mod, "time", clock)
    steps = []
    admit = eng._admit
    monkeypatch.setattr(eng, "_admit", lambda: (steps.append(1), admit())[1])
    c0 = _engine_counters()
    _run(eng, [([5, 6, 7, 8], 12, dict(top_k=1, termination_id=10 ** 9))])
    c1 = _engine_counters()
    ticks = int(c1["ticks"] - c0["ticks"])
    assert 12 <= ticks <= len(steps)
    # one read a step (admit's start: all that a step with nothing to
    # launch reads) and three more a launched tick
    assert len(reads) == len(steps) + 3 * ticks, (len(reads), len(steps))


def test_lru_leaf_first_eviction_order(toy_model):
    """Direct pool+trie unit test: eviction takes refcount-0 LEAVES in LRU
    order and never touches referenced pages."""
    cfg, params = toy_model
    pool = PagedKVPool(cfg, num_pages=12, page_size=4)
    cache = PrefixCache(pool, page_size=4)
    a = pool.alloc(3)  # chain A: 3 pages
    b = pool.alloc(2)  # chain B: 2 pages
    cache.insert(list(range(100, 112)), a, 3)
    cache.insert(list(range(200, 208)), b, 2)
    pool.release(a)
    pool.release(b)
    assert pool.num_evictable == 5
    # touch chain A so B becomes LRU
    got = cache.match(list(range(100, 112)), 3)
    assert got == a
    pool.release(got)
    freed = cache.evict(2)
    assert freed == [b[1], b[0]], "leaf-first LRU should drain chain B"
    # a referenced leaf is untouchable
    got = cache.match(list(range(100, 112)), 3)
    freed = cache.evict(10)
    assert freed == [] and len(cache) == 3
    pool.release(got)
    # now the whole A chain unwinds leaf-first
    assert cache.evict(10) == [a[2], a[1], a[0]]
    # evicted pages belong to the caller (alloc feeds them to the free
    # list); the trie is empty and nothing is cached or referenced
    assert len(cache) == 0 and not pool.cached
    assert int(pool.refcounts.sum()) == 0


def _scan_victims(cache, pool, n):
    """The reference: the victim scan as it stood before the idle leaves
    were kept in order (a whole pass over the trie a victim, the lowest
    ``last_use`` among childless unreferenced nodes, first in ``_nodes``
    order on a tie), run on the live trie without unlinking anything."""
    gone, out = set(), []
    while len(out) < n:
        victim, stamps = None, set()
        for node in cache._nodes.values():
            if id(node) in gone or pool.refcounts[node.page] != 0:
                continue
            if any(id(c) not in gone for c in node.children.values()):
                continue
            assert node.last_use not in stamps, "two idle leaves, one stamp"
            stamps.add(node.last_use)
            if victim is None or node.last_use < victim.last_use:
                victim = node
        if victim is None:
            break
        gone.add(id(victim))
        out.append(victim.page)
    return out


def _assert_pool_and_trie(pool, cache):
    """(b) the kept count equals the walk's; (c) free, referenced and
    cached-idle are disjoint and cover the pool; the idle order's bound."""
    walk = sum(1 for p in pool.cached if pool.refcounts[p] == 0)
    assert pool.num_evictable == walk
    assert pool.num_available == pool.num_free + walk
    free = set(pool._free)
    assert len(free) == pool.num_free and NULL_PAGE not in free
    referenced = {p for p in range(1, pool.num_pages) if pool.refcounts[p]}
    idle = {p for p in pool.cached if pool.refcounts[p] == 0}
    assert not free & referenced and not free & pool.cached
    assert len(free) + len(referenced) + len(idle) == pool.num_pages - 1
    assert set(cache._nodes) == pool.cached
    assert len(cache._idle) <= 2 * len(cache) + 64


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_eviction_equals_the_reference_scan_over_random_steps(toy_model, seed):
    """A pool and a trie through a few thousand random steps: chains that
    share prefixes admitted (match, alloc past the free list, insert),
    retired in shuffled order, touched, referenced bare, and evicted in
    part.  Every eviction's victims are the reference scan's, page for
    page; the kept count is the walk's; the page states stay disjoint."""
    cfg, _ = toy_model
    ps, n_pages = 2, 72
    pool = PagedKVPool(cfg, num_pages=n_pages, page_size=ps)
    cache = PrefixCache(pool, page_size=ps)
    rng = np.random.default_rng(seed)
    held = []                       # page lists some "request" references
    evictions = victims = 0

    def chain():
        # 6 families x a few branch points: prefixes are shared, tails differ
        fam, depth = int(rng.integers(0, 6)), int(rng.integers(1, 7))
        toks = []
        for d in range(depth):
            branch = int(rng.integers(0, 2 if d < 3 else 4))
            toks += [100 * fam + 10 * d + branch] * ps
        return toks, depth

    for step in range(2500):
        op = rng.choice(["admit", "retire", "touch", "evict", "bare"],
                        p=[0.36, 0.32, 0.14, 0.1, 0.08])
        if op == "admit":
            toks, depth = chain()
            matched = cache.match(toks, int(rng.integers(0, depth + 1)))
            need = depth - len(matched) + int(rng.integers(0, 3))
            short = need - pool.num_free
            # a grant the count cannot cover evicts nothing; one it can
            # takes the scan's victims, which fall short of the count
            # where an idle page's child is still referenced (a request
            # whose duplicate pages stayed private holds the child alone)
            walk = sum(1 for p in pool.cached if pool.refcounts[p] == 0)
            want = _scan_victims(cache, pool, short) if (
                0 < short <= walk) else []
            cached_before = set(pool.cached)
            fresh = pool.alloc(need)
            assert cached_before - pool.cached == set(want), (step, want)
            if want:
                evictions, victims = evictions + 1, victims + len(want)
            if fresh is None:
                assert short > len(want)
                assert list(pool._free)[pool.num_free - len(want):] == want
                pool.release(matched)
            else:
                assert fresh[need - len(want):] == want, (step, want, fresh)
                pages = matched + fresh
                cache.insert(toks, pages, int(rng.integers(0, depth + 1)))
                held.append(pages)
        elif op == "retire" and held:
            pool.release(held.pop(int(rng.integers(0, len(held)))))
        elif op == "touch":
            toks, depth = chain()
            pool.release(cache.match(toks, depth))
        elif op == "evict":
            k = int(rng.integers(1, 6))
            want = _scan_victims(cache, pool, k)
            got = cache.evict(k)
            assert got == want, (step, want, got)
            pool._free.extend(got)  # evicted pages belong to the caller
        elif op == "bare" and pool.cached:
            # references that stamp nothing (the handoff, parking): on a
            # page and, as every holder does, on all its ancestors
            p = sorted(pool.cached)[int(rng.integers(0, len(pool.cached)))]
            node, path = cache._nodes[p], []
            while node is not cache.root:
                path.append(node.page)
                node = node.parent
            pool.incref(path)
            held.append(path)
        _assert_pool_and_trie(pool, cache)
    assert evictions > 50 and victims > evictions, "the pool never ran dry"
    for pages in held:
        pool.release(pages)
    _assert_pool_and_trie(pool, cache)
    # nothing is referenced: the whole trie unwinds, still in the order
    want = _scan_victims(cache, pool, n_pages)
    assert cache.evict(n_pages) == want
    assert len(cache) == 0 and not pool.cached and not cache._idle
    assert int(pool.refcounts.sum()) == 0


def test_order_of_use_not_order_of_retirement_decides(toy_model):
    """A matches at clock 5, B on another path at clock 6, B retires
    first: B's leaf became idle before A's, and A's still goes first."""
    cfg, _ = toy_model
    pool = PagedKVPool(cfg, num_pages=12, page_size=4)
    cache = PrefixCache(pool, page_size=4)
    toks_a, toks_b = list(range(100, 108)), list(range(200, 208))
    a, b = pool.alloc(2), pool.alloc(2)
    cache.insert(toks_a, a, 2)
    cache.insert(toks_b, b, 2)
    pool.release(a)
    pool.release(b)
    got_a = cache.match(toks_a, 2)       # A is used first ...
    got_b = cache.match(toks_b, 2)       # ... B after it
    pool.release(got_b)                  # B retires first
    pool.release(got_a)
    assert cache.evict(1) == [a[1]], "the least recently USED leaf goes"
    assert cache.evict(3) == [a[0], b[1], b[0]]


def test_idle_order_stays_bounded_without_any_eviction(toy_model):
    """A pool that never runs dry never pops: 10,000 match / release
    rounds leave one stale entry each, and the rebuild keeps the heap
    under its bound; what is left afterwards still evicts in order."""
    from megatron_llm_tpu.observability import registry as registry_mod

    cfg, _ = toy_model
    pool = PagedKVPool(cfg, num_pages=40, page_size=2)
    cache = PrefixCache(pool, page_size=2)
    chains = []
    for c in range(6):
        pages = pool.alloc(4)
        toks = [10 * c + d for d in range(4) for _ in range(2)]
        cache.insert(toks, pages, 4)
        pool.release(pages)
        chains.append((toks, pages))
    rebuilds = registry_mod.get_registry().counter(
        "mlt_engine_prefix_idle_rebuilds_total")
    before, peak = rebuilds.value, 0
    rng = np.random.default_rng(0)
    for _ in range(10_000):
        toks, pages = chains[int(rng.integers(0, 6))]
        assert cache.match(toks, 4) == pages
        pool.release(pages)
        peak = max(peak, len(cache._idle))
    assert peak <= 2 * len(cache) + 64 == 112
    assert rebuilds.value - before >= 10_000 // 112
    assert pool.num_evictable == 24 and pool.num_free == 15
    want = _scan_victims(cache, pool, 24)
    assert cache.evict(24) == want and len(want) == 24 and not cache._idle


def test_queue_overflow_raises_engine_overloaded(toy_model):
    cfg, params = toy_model
    eng = _engine(cfg, params, max_queue=2)
    eng.submit([2, 3], 4, top_k=1)
    eng.submit([2, 4], 4, top_k=1)
    with pytest.raises(EngineOverloaded):
        eng.submit([2, 5], 4, top_k=1)
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# Chunked prefill scheduling
# ---------------------------------------------------------------------------


def test_prefill_interleaves_with_decode(toy_model):
    """Active decode slots keep generating while a long prompt prefills one
    chunk per tick — the monolithic stall is gone."""
    cfg, params = toy_model
    eng = _engine(cfg, params, max_slots=2, max_seq=256,
                  prefill_chunk=16, prefix_cache=False)
    short = eng.submit([2, 3, 4], 200, top_k=1, termination_id=10 ** 9)
    # admit + activate the short request
    while not short.generated:
        eng.step()
    long_prompt = [2 + (j * 7) % 60 for j in range(160)]  # 10 chunks
    long_req = eng.submit(long_prompt, 4, top_k=1, termination_id=10 ** 9)
    gen_before = len(short.generated)
    while long_req._phase in ("queued", "prefill"):
        eng.step()
    grown = len(short.generated) - gen_before
    assert grown >= 8, (
        f"decode stalled during chunked prefill (only {grown} tokens while "
        f"10 chunks filled)")
    eng.run_until_idle()
    long_req.result(timeout=30)
    short.result(timeout=30)
