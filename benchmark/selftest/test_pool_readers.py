"""The readers of the pool's and the scheduler's whole-window counters
(PR 36): each returns None on a run whose counters lack the name (the
parent's) and the stated quotient on counters made by hand; and the
``pool-reclaim`` span a slow-path ``alloc`` draws is read back, with its
arguments and its zero-length ``pool-evict`` child, from a small capture
taken here on the CPU."""

import glob
import os
import types
import warnings

import pytest

from benchmark.lib import cells, per_tick, spans

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
NEW = ("pool_dry_tick_share.batch", "pool_reclaim_ms.batch",
       "evict_scan_per_victim.batch", "plan_upload_ms.batch",
       "host_offcpu_ms.batch")
PHASE = 'mlt_engine_tick_phase_seconds_sum{phase="%s"}'
CPU = 'mlt_engine_tick_host_cpu_seconds_sum{side="%s"}'
# what the parent's /metrics gives a window of 2,000 ticks
PARENT = {"mlt_engine_ticks_total": 2000.0,
          PHASE % "admit": 4.0, PHASE % "plan": 22.0, PHASE % "launch": 6.0,
          PHASE % "apply": 3.6, PHASE % "fetch": 17.0,
          "mlt_engine_free_pages": -9000.0}
# and what this PR's adds to it
CHANGE = {**PARENT,
          "mlt_engine_pool_dry_ticks_total": 700.0,
          'mlt_engine_pool_scan_seconds_total{what="evictable"}': 3.0,
          'mlt_engine_pool_scan_seconds_total{what="evict"}': 9.0,
          'mlt_engine_pool_alloc_pages_total{source="free"}': 9000.0,
          'mlt_engine_pool_alloc_pages_total{source="evict"}': 4000.0,
          "mlt_engine_prefix_evicted_pages_total": 4000.0,
          "mlt_engine_prefix_evict_scanned_nodes_total": 44.0e6,
          'mlt_engine_plan_part_seconds_sum{part="prefill"}': 5.0,
          'mlt_engine_plan_part_seconds_sum{part="pages"}': 10.0,
          'mlt_engine_plan_part_seconds_sum{part="upload"}': 6.4,
          CPU % "dispatch": 24.0, CPU % "apply": 1.6}
WANT = {"pool_dry_tick_share.batch": 35.0,       # 700 / 2000
        "pool_reclaim_ms.batch": 6.0,            # (3 + 9) s / 2000
        "evict_scan_per_victim.batch": 11000.0,  # 44e6 / 4000
        "plan_upload_ms.batch": 3.2,             # 6.4 s / 2000
        "host_offcpu_ms.batch": 5.0}             # (35.6 - 25.6) s / 2000


def _reader(name):
    return cells.Cell.reader_at(os.path.join(LAYER_METRICS, name + ".py"))


def _run(counters):
    return types.SimpleNamespace(counters=dict(counters), trace=None)


@pytest.mark.parametrize("name", NEW)
def test_reader_leaves_the_metric_out_on_the_parent(name):
    assert _reader(name).reduce(_run(PARENT)) is None
    assert _reader(name).reduce(_run({})) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_the_stated_quotient(name):
    assert _reader(name).reduce(_run(CHANGE)) == pytest.approx(WANT[name])


def test_readings_on_a_window_that_never_ran_dry():
    quiet = {**CHANGE, "mlt_engine_pool_dry_ticks_total": 0.0,
             'mlt_engine_pool_scan_seconds_total{what="evict"}': 0.0,
             "mlt_engine_prefix_evicted_pages_total": 0.0,
             "mlt_engine_prefix_evict_scanned_nodes_total": 0.0}
    run = _run(quiet)
    assert _reader("pool_dry_tick_share.batch").reduce(run) == 0.0
    assert _reader("evict_scan_per_victim.batch").reduce(run) is None
    # admission's walk over the cached pages is paid in both regimes
    assert _reader("pool_reclaim_ms.batch").reduce(run) == pytest.approx(1.5)


def test_a_window_without_ticks_reads_nothing():
    run = _run({**CHANGE, "mlt_engine_ticks_total": 0.0})
    for name in NEW:
        if name != "evict_scan_per_victim.batch":
            assert _reader(name).reduce(run) is None, name


def test_per_tick_wants_every_name():
    run = _run(CHANGE)
    assert per_tick.total(run, [PHASE % "admit", PHASE % "plan"]) == 26.0
    assert per_tick.total(run, [PHASE % "admit", "no_such_total"]) is None
    assert per_tick.ms(run, [PHASE % "apply"], [CPU % "apply"]) == \
        pytest.approx(1.0)
    assert per_tick.ms(run, [PHASE % "plan"], ["no_such_total"]) is None


def test_the_readings_are_consistent_with_the_phase_sums():
    """What the issue's acceptance criteria ask of every traced run holds
    for the hand-made window: reclaim + off-CPU fit inside host work, the
    upload inside plan."""
    run = _run(CHANGE)
    work = _reader("host_work_ms.batch").reduce(run)
    assert work == pytest.approx(17.8)
    assert (_reader("pool_reclaim_ms.batch").reduce(run)
            + _reader("host_offcpu_ms.batch").reduce(run)) <= work
    assert _reader("plan_upload_ms.batch").reduce(run) <= \
        1e3 * CHANGE[PHASE % "plan"] / CHANGE["mlt_engine_ticks_total"]


def test_pool_reclaim_is_read_back_from_a_cpu_capture(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from megatron_llm_tpu.generation.engine import PagedKVPool, PrefixCache
    from megatron_llm_tpu.models import make_config
    from megatron_llm_tpu.observability import trace as trace_mod

    cfg = make_config(
        "llama2", num_layers=1, hidden_size=32, num_attention_heads=2,
        num_attention_heads_kv=1, ffn_hidden_size=64, seq_length=64,
        max_position_embeddings=64, vocab_size=64,
        params_dtype="float32", use_flash_attn=False)
    pool = PagedKVPool(cfg, num_pages=32, page_size=4)
    cache = PrefixCache(pool, page_size=4)
    pages = pool.alloc(24)
    cache.insert(list(range(96)), pages, 24)
    pool.release(pages)                       # 24 cached-idle, 7 free
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace_mod.span("engine-step", tick=0):
            with trace_mod.span("engine-plan"):
                assert len(pool.alloc(3)) == 3    # off the free list
                assert len(pool.alloc(9)) == 9    # 4 free: 5 short
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        profile = ProfileData.from_file(path)
    # the names every cell's readers ask for do not hold it ...
    assert not [s for s in spans.from_profile(profile)
                if s.name.startswith("pool-")]
    # ... a reader that wants it names it
    got = spans.from_profile(
        profile, names=spans.SPAN_NAMES | {"pool-reclaim", "pool-evict"})
    assert [s.name for s in got] == ["engine-step", "engine-plan",
                                     "pool-reclaim", "pool-evict"]
    _, plan, reclaim, evict = got
    assert reclaim.parent is plan and evict.parent is reclaim
    assert {k: int(v) for k, v in reclaim.args.items()} == {
        "want": 9, "free": 4, "cached": 24}
    # a chain: each victim is the one idle leaf, popped off the heap (one
    # entry looked at a victim since PR 37; a pass over the trie before it)
    assert {k: int(v) for k, v in evict.args.items()} == {
        "evicted": 5, "scanned": 5}
    assert evict.end - evict.start < reclaim.end - reclaim.start
