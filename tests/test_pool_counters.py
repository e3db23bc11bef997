"""The KV pool and the prefix trie count and time their own slow path
(ISSUE 36): pages by where they came from, the eviction's seconds, the
trie's victims and the entries it looked at, the dry flag, and the
``pool-reclaim`` span with its ``pool-evict`` child in the ring and in a
``jax.profiler`` capture.  Since ISSUE 37 the count of evictable pages is
kept and the idle leaves are kept in order, so an eviction looks at one
or two entries a victim whatever the trie holds, and counting reads no
clock.  Host only: a pool and a trie, no engine."""

import glob
import os
import threading
import warnings

import pytest

from megatron_llm_tpu.generation import pools as pools_mod
from megatron_llm_tpu.generation.engine import PagedKVPool, PrefixCache
from megatron_llm_tpu.models import make_config
from megatron_llm_tpu.observability import registry as registry_mod
from megatron_llm_tpu.observability import trace as trace_mod

PS = 4
CHAINS, DEPTH = 10, 5   # 50 cached-idle pages of 63, 13 left free


@pytest.fixture(scope="module")
def cfg():
    return make_config(
        "llama2", num_layers=1, hidden_size=32, num_attention_heads=2,
        num_attention_heads_kv=1, ffn_hidden_size=64, seq_length=64,
        max_position_embeddings=64, vocab_size=64,
        params_dtype="float32", use_flash_attn=False)


def _dry_pool(cfg, chains=CHAINS):
    """64 pages (63 usable): ten chains of five pages in the trie, all
    released, so 50 are cached-idle and 13 free.  (More chains: as many
    more pages, 13 free still.)"""
    pool = PagedKVPool(cfg, num_pages=chains * DEPTH + 14, page_size=PS)
    cache = PrefixCache(pool, page_size=PS)
    for c in range(chains):
        pages = pool.alloc(DEPTH)
        toks = [1000 * (c + 1) + i for i in range(DEPTH * PS)]
        assert cache.insert(toks, pages, DEPTH) == DEPTH
        pool.release(pages)
    assert pool.num_free == 13 and len(cache) == chains * DEPTH
    return pool, cache


class NoClock:
    """Stands in for the engine module's ``time``: any read fails."""

    def __getattr__(self, name):
        raise AssertionError(f"read time.{name}")


def _read():
    reg = registry_mod.get_registry()
    out = {"free": reg.counter("mlt_engine_pool_alloc_pages_total",
                               labels={"source": "free"}).value,
           "evict": reg.counter("mlt_engine_pool_alloc_pages_total",
                                labels={"source": "evict"}).value,
           "evicted": reg.counter(
               "mlt_engine_prefix_evicted_pages_total").value,
           "scanned": reg.counter(
               "mlt_engine_prefix_evict_scanned_nodes_total").value}
    for what in ("evictable", "evict"):
        out["s_" + what] = reg.counter(
            "mlt_engine_pool_scan_seconds_total",
            labels={"what": what}).value
    return out


def _delta(before):
    after = _read()
    return {k: after[k] - before[k] for k in after}


def test_alloc_past_the_free_list_counts_its_eviction(cfg):
    pool, cache = _dry_pool(cfg)
    before = _read()
    assert not pool.reclaimed
    got = pool.alloc(20)                  # 13 free: 7 short
    d = _delta(before)
    assert len(got) == 20 and pool.num_free == 0
    assert d["free"] == 13 and d["evict"] == 7
    assert d["evicted"] == 7 and len(cache) == 43
    # one entry of the idle order a victim (the oldest chain leaf first,
    # then the parent each eviction exposes), not a pass over 50 nodes
    assert d["scanned"] == 7
    assert d["s_evictable"] == 0 and d["s_evict"] > 0
    assert pool.reclaimed, "the step that launches next counts a dry tick"


def test_alloc_off_the_free_list_reads_no_clock(cfg, monkeypatch):
    pool, cache = _dry_pool(cfg)
    before = _read()
    monkeypatch.setattr(pools_mod, "time", NoClock())
    got = pool.alloc(13)                  # exactly what is free
    monkeypatch.undo()
    d = _delta(before)
    assert len(got) == 13
    assert d == {"free": 13, "evict": 0, "evicted": 0, "scanned": 0,
                 "s_evictable": 0, "s_evict": 0}
    assert not pool.reclaimed and len(cache) == 50
    # and the next page is the slow path: it does read the clock
    monkeypatch.setattr(pools_mod, "time", NoClock())
    with pytest.raises(AssertionError, match="read time.perf_counter"):
        pool.alloc(1)


def test_alloc_beyond_what_is_available_evicts_nothing(cfg, monkeypatch):
    pool, cache = _dry_pool(cfg)
    before = _read()
    # refused on the kept count: no eviction, and no clock to time one
    monkeypatch.setattr(pools_mod, "time", NoClock())
    assert pool.alloc(64) is None         # 13 free + 50 evictable = 63
    monkeypatch.undo()
    d = _delta(before)
    assert d["free"] == d["evict"] == d["evicted"] == d["scanned"] == 0
    assert d["s_evictable"] == 0 and d["s_evict"] == 0
    assert not pool.reclaimed and pool.num_free == 13 and len(cache) == 50


def test_referenced_pages_are_scanned_and_never_evicted(cfg):
    """The referenced chain's leaf left a stale entry in the idle order:
    the eviction looks at it, counts it and passes it over; the pages
    granted by eviction equal the trie's own count of victims."""
    pool, cache = _dry_pool(cfg)
    held = cache.match([1000 + i for i in range(DEPTH * PS)], DEPTH)
    assert len(held) == DEPTH and pool.num_evictable == 45
    before = _read()
    got = pool.alloc(13 + 45)             # everything that can be had
    d = _delta(before)
    assert len(got) == 58 and d["evict"] == d["evicted"] == 45
    assert d["scanned"] == 45 + 1         # a victim each, and the stale one
    assert len(cache) == DEPTH and not set(got) & set(held)
    assert pool.alloc(1) is None


def test_the_count_is_read_without_a_clock_or_a_walk_by_any_caller(
        cfg, monkeypatch):
    """/health answers ``num_available`` on a handler thread, admission
    on the scheduler's: both read a kept number."""
    pool, cache = _dry_pool(cfg)
    held = cache.match([1000 + i for i in range(DEPTH * PS)], 2)

    class NoWalk(set):
        def __iter__(self):
            raise AssertionError("walked the cached pages")

    pool.cached = NoWalk(pool.cached)
    before = _read()
    monkeypatch.setattr(pools_mod, "time", NoClock())
    seen = [pool.num_available, pool.num_evictable]
    th = threading.Thread(target=lambda: seen.append(pool.num_available))
    th.start()
    th.join()
    monkeypatch.undo()
    assert seen == [61, 48, 61]
    assert all(v == 0 for v in _delta(before).values())
    pool.release(held)
    assert pool.num_available == 63


@pytest.mark.parametrize("stale", [0, 3])
def test_an_evictions_cost_does_not_grow_with_the_trie(cfg, stale):
    """Scanned per victim is the same at 50 and at 5,000 cached pages:
    a victim each, plus the entries that went stale (chains matched and
    released since: each left its old entry behind)."""
    per_victim = []
    for chains in (CHAINS, 100 * CHAINS):
        pool, cache = _dry_pool(cfg, chains)
        for c in range(stale):            # the three oldest, used again
            toks = [1000 * (c + 1) + i for i in range(DEPTH * PS)]
            pool.release(cache.match(toks, DEPTH))
        before = _read()
        assert len(pool.alloc(13 + 12)) == 25
        d = _delta(before)
        assert d["evicted"] == 12 and len(cache) == chains * DEPTH - 12
        per_victim.append(d["scanned"] / d["evicted"])
    assert per_victim == [(12 + stale) / 12] * 2


def test_not_publishing_counts_nothing_and_still_sets_the_flag(cfg):
    pool, _ = _dry_pool(cfg)
    before = _read()
    registry_mod.set_publishing(False)
    try:
        assert len(pool.alloc(15)) == 15
    finally:
        registry_mod.set_publishing(True)
    assert all(v == 0 for v in _delta(before).values())
    assert pool.reclaimed


def test_reclaim_span_and_its_child_land_in_the_ring(cfg):
    pool, _ = _dry_pool(cfg)
    old = trace_mod.get_tracer()
    ring = trace_mod.configure(capacity=256)
    try:
        pool.alloc(5)                     # the free list serves it: no span
        assert not [e for e in ring.snapshot() if e[1].startswith("pool-")]
        pool.alloc(10)                    # 8 left free: 2 short
        events = [e for e in ring.snapshot() if e[1].startswith("pool-")]
    finally:
        trace_mod._TRACER = old
    by = {e[1]: e for e in events}
    assert sorted(by) == ["pool-evict", "pool-reclaim"] and len(events) == 2
    outer, inner = by["pool-reclaim"], by["pool-evict"]
    assert outer[5] == {"want": 10, "free": 8, "cached": 50}
    assert inner[5] == {"evicted": 2, "scanned": 2}
    assert outer[2] <= inner[2] and inner[2] + inner[3] <= outer[2] + outer[3]


def test_reclaim_span_is_read_back_from_a_capture(cfg, tmp_path):
    """The benchmark's own reader (lib/spans.py ``from_profile``) finds
    ``pool-reclaim`` with its arguments in a jax.profiler capture, and
    the zero-length ``pool-evict`` nested in it."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.lib import spans as spans_mod

    pool, _ = _dry_pool(cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace_mod.span("engine-plan"):
            pool.alloc(16)                # 13 free: 3 short
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = spans_mod.from_profile(
            ProfileData.from_file(path),
            names=("engine-plan", "pool-reclaim", "pool-evict"))
    assert [s.name for s in got] == ["engine-plan", "pool-reclaim",
                                     "pool-evict"]
    plan, reclaim, evict = got
    assert reclaim.parent is plan and evict.parent is reclaim
    assert {k: int(v) for k, v in reclaim.args.items()} == {
        "want": 16, "free": 13, "cached": 50}
    assert {k: int(v) for k, v in evict.args.items()} == {
        "evicted": 3, "scanned": 3}
    assert reclaim.end - reclaim.start > 0
