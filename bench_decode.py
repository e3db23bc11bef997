"""Continuous-batching engine benchmark — prints ONE JSON line for the driver.

Two modes:

* ``--mode occupancy`` (default, ISSUE 1 headline): decode tokens/sec of
  the paged-KV continuous-batching engine (generation/engine.py) at full
  occupancy (8 concurrent requests), on the 470M bench model.  Rows sweep
  occupancy (1 / 4 / 8 concurrent requests) and report per-tick latency
  alongside throughput; every row also times the SEQUENTIAL per-request
  dense path (generation.generate_tokens, one call per request — the
  legacy server shape) on the same requests, so ``speedup_vs_sequential``
  is an apples-to-apples continuous-batching win on identical hardware and
  weights.  Gate: >= 3x sequential at 8 concurrent.

* ``--mode shared_prefix`` (ISSUE 5): N concurrent requests sharing a long
  system prompt (distinct tails), against a cache WARMED by one prior
  request — the production steady state where the system prompt is hot.
  Reports prefill-tokens-computed, per-request TTFT, and prefix hit rate
  for the prefix-cache-ON engine vs the same engine with the cache OFF.
  Gate: >= 2x reduction in prefill tokens computed and improved aggregate
  TTFT at >= 8 concurrent shared-prefix requests.  (Concurrent COLD
  arrivals do not dedup in-flight prefills — admission only matches pages
  already cached — which is why the cache is warmed first.)

* ``--mode slo`` (ISSUE 7): mixed-priority overload — batch requests
  (priority 2, long generations, no deadline) fill every slot, then
  interactive requests (priority 0, TTFT deadline) arrive.  The same
  traffic runs through the ``fcfs``, ``priority``, and ``slo`` scheduling
  policies (generation/scheduling/); each row reports per-class p50/p99
  TTFT, deadline-miss rate, preemption and shed counts.  Headline:
  high-priority p99 TTFT speedup of ``slo`` over ``fcfs`` (priority-class
  reordering + preemption-by-page-release).  Gate: >= 2x.

* ``--mode spec`` (ISSUE 9): speculative decoding on vs off on the same
  greedy traffic at each occupancy level.  The draft is a 1-layer
  same-width model and the target is its identity extension
  (speculative/draft.extend_params_identity) so greedy acceptance is
  provably 100% on random-init weights — the honest way to measure the
  *mechanics* (draft-loop cost, fused verify, multi-token ticks) rather
  than a particular model pair's agreement; the measured acceptance rate
  rides along in the evidence.  Rows report decode tok/s, tokens per
  tick, and per-request p50/p99 latency for both arms.  Headline:
  decode tok/s speedup at concurrency 1 — the latency-bound shape
  speculative decoding exists for.  Gate: >= 1.3x.

* ``--mode capacity`` (ISSUE 13): concurrent-user capacity at a FIXED
  pool byte budget, ``--kv_dtype int8`` vs ``bf16``.  The budget is what
  a bf16 pool of the reference size occupies; each arm gets as many
  pages as its storage mode fits into those bytes (per-page scale
  overhead charged to the int8 arm; CPU sanity computes in f32 but
  budgets pages by the honest bf16/int8 accounting a TPU would see).
  Section 1 saturates the pool with more requests than fit and records
  the PEAK concurrent decode slots each arm sustains — the commitment
  ledger turns pool bytes directly into admission concurrency, so this
  is the "concurrent users per chip" number.  Section 2 replays a
  round-robin multi-tenant shared-prefix workload where the byte budget
  bounds how many groups' prompt pages stay cached — the prefix hit
  rate is the capacity lever's second dividend.  The in-bench
  losslessness assert pins int8 greedy tokens == bf16 greedy tokens on
  the workload.  Gate: >= 2x peak concurrent slots at equal bytes, hit
  rate no worse.

* ``--mode router`` (ISSUE 10): a 2-replica fleet (each a real
  continuous-batching engine behind a real MegatronServer on an ephemeral
  port) fronted by the cross-replica router (serving/router/), on the
  fleet version of the shared-prefix workload: G prompt groups, each
  sharing a long system prompt with distinct tails.  The same traffic runs
  through ``prefix_affinity`` (consistent hashing on the prompt prefix)
  and ``round_robin``; each arm reports the FLEET-wide prefix-hit rate and
  client-observed mean/p99 TTFT (non-streaming replicas deliver the whole
  body at first byte, so time-to-response is the TTFT the client sees).
  After the comparison, one replica is killed mid-run (listening socket
  closed) under continued traffic: the failover section must show zero
  dropped requests and the breaker ejecting the dead replica.  Gate:
  prefix_affinity beats round_robin on BOTH fleet hit rate and mean TTFT,
  and the failover drops nothing.

* ``--mode disagg`` (ISSUE 19): disaggregated prefill/decode serving — a
  mixed workload (saturated short-prompt decode class + long-prompt
  prefill class) through a unified 2-replica fleet vs a 1-prefill +
  1-decode split at equal chip count, both behind the ``disagg`` router
  policy.  Long prompts in the split arm take the
  prefill→KV-push→decode path (serving/handoff/); the decode replica's
  tick stream then stays pure decode.  Rows report per-class TTFT,
  decode-class TPOT, and client latency for both arms; the in-bench
  identity assert pins every text byte-equal across arms.  Headline:
  decode-class p99 TPOT speedup, split over unified.  Gate: > 1x with
  zero handoff failures.

Same device contract as bench.py (``bench.probe_backend``); a watchdog
turns hangs into structured error lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import (  # noqa: E402
    cpu_contract_line,
    probe_backend,
)

METRIC = "engine_decode_tok_s_llama470m_c8_1chip"
METRIC_CAPACITY = "engine_kv_capacity_slot_ratio_llama470m_1chip"
METRIC_PREFIX = "engine_prefix_prefill_reduction_llama470m_c8_1chip"
METRIC_SLO = "engine_slo_hi_p99_ttft_speedup_llama470m_1chip"
METRIC_SPEC = "engine_spec_decode_speedup_llama470m_c1_1chip"
METRIC_ROUTER = "router_prefix_affinity_ttft_speedup_llama470m_2rep_1chip"
METRIC_STREAMING = "serving_stream_first_token_speedup_llama470m_c8_2rep_1chip"
METRIC_DISAGG = "serving_disagg_decode_p99_tpot_speedup_llama470m_2rep_1chip"
METRIC_PP = "engine_pp_decode_tok_s_ratio_llama470m_c4_eqchip"

# every mode decodes greedily with termination disabled: runs are
# workload-shaped, never content-shaped
GREEDY_KW = dict(top_k=1, termination_id=0, use_eod_for_termination=False)


def make_engine(cfg, params, tokenizer=None, **engine_kw):
    """THE engine construction point shared by every bench mode — one
    place to thread geometry/policy/spec knobs, so modes can't drift
    apart in setup.  Router mode passes a tokenizer (its traffic arrives
    as HTTP text); the direct-submit modes run tokenless."""
    from megatron_llm_tpu.generation import ContinuousBatchingEngine

    return ContinuousBatchingEngine(cfg, params, tokenizer, **engine_kw)


def run_workload(eng, jobs, timeout: float = 600.0):
    """Submit ``(prompt, gen, kwargs)`` jobs, drive the engine to idle on
    this thread, wait on every future; returns the request objects (their
    ttft/latency telemetry is the modes' raw material)."""
    reqs = [eng.submit(p, g, **kw) for p, g, kw in jobs]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=timeout)
    return reqs


def _requests(num: int, prompt: int, gen: int, vocab: int, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, prompt)]
            for _ in range(num)]


def bench_capacity(cfg, params, n_requests: int, ref_slots: int,
                   prompt: int, gen: int, vocab: int, groups: int,
                   per_group: int, shared_len: int, tail_len: int,
                   gen_cache: int) -> dict:
    """Concurrent capacity + prefix-cache hit rate at FIXED pool bytes,
    int8 vs bf16 KV storage (ISSUE 13 — see module docstring)."""
    import jax.numpy as jnp
    import numpy as np

    from megatron_llm_tpu.generation.engine import PagedKVPool

    page = cfg.inference.page_size
    max_seq = max(prompt + gen, shared_len + tail_len + gen_cache)
    pages_per_seq = -(-max_seq // page)

    def bytes_per_page(kv_dtype: str) -> float:
        # honest accounting probe: tiny pool in the TPU storage dtypes
        # (bf16 values even on the f32 CPU-sanity host), scale overhead
        # charged to the quantized arm
        probe = PagedKVPool(cfg, 2, page, dtype=jnp.bfloat16,
                            kv_dtype=kv_dtype)
        return (probe.kv_pool_bytes() + probe.kv_scale_bytes()) / 2.0

    # THE fixed budget: what a bf16 pool sized for ref_slots concurrent
    # sequences occupies — both arms must live inside these bytes
    budget = int(bytes_per_page("bf16") * (ref_slots * pages_per_seq + 1))

    def pages_for(kv_dtype: str) -> int:
        return max(int(budget // bytes_per_page(kv_dtype)), 2)

    prompts = _requests(n_requests, prompt, gen, vocab, seed=7)

    def run_concurrency(kv_dtype: str) -> dict:
        num_pages = pages_for(kv_dtype)
        eng = make_engine(cfg, params, max_slots=n_requests,
                          max_seq=max_seq, num_pages=num_pages,
                          prefix_cache=False, kv_dtype=kv_dtype)
        t0 = time.perf_counter()
        reqs = run_workload(eng, [(p, gen, dict(GREEDY_KW))
                                  for p in prompts])
        wall = time.perf_counter() - t0
        # the engine's own high-water mark (also on /health), maintained
        # under its lock — no private-state sampling from the bench
        peak, ticks = eng.peak_active_slots, eng.ticks
        outs = [(r.prompt + r.generated, r.log_probs) for r in reqs]
        return {
            "kv_dtype": kv_dtype,
            "pool_budget_bytes": budget,
            "num_pages": num_pages,
            "kv_pool_bytes": eng.pool.kv_pool_bytes(),
            "kv_scale_bytes": eng.pool.kv_scale_bytes(),
            "peak_concurrent_slots": peak,
            "wall_s": round(wall, 4),
            "ticks": ticks,
            "decode_tok_s": round(n_requests * gen / wall, 1),
            "tokens": [t for t, _ in outs],
        }

    rng = np.random.default_rng(11)
    shared = [[int(t) for t in rng.integers(1, vocab, shared_len)]
              for _ in range(groups)]
    tails = [[int(t) for t in rng.integers(1, vocab, tail_len)]
             for _ in range(groups * per_group)]

    def run_cache(kv_dtype: str) -> dict:
        # round-robin multi-tenant revisits: the byte budget decides how
        # many tenants' prompt pages survive in the trie between visits
        num_pages = pages_for(kv_dtype)
        eng = make_engine(cfg, params, max_slots=2, max_seq=max_seq,
                          num_pages=num_pages, kv_dtype=kv_dtype)
        for g in range(groups):  # warm each tenant once
            run_workload(eng, [(shared[g] + tails[g], gen_cache,
                                dict(GREEDY_KW))])
        hit0, miss0 = eng.prefix_hit_tokens, eng.prefix_miss_tokens
        i = groups
        for r in range(per_group - 1):
            for g in range(groups):
                run_workload(eng, [(shared[g] + tails[i], gen_cache,
                                    dict(GREEDY_KW))])
                i += 1
        hit = eng.prefix_hit_tokens - hit0
        miss = eng.prefix_miss_tokens - miss0
        return {
            "kv_dtype": kv_dtype,
            "num_pages": num_pages,
            "hit_tokens": hit,
            "miss_tokens": miss,
            "hit_rate": round(hit / max(hit + miss, 1), 4),
            "pages_cached_end": len(eng.pool.cached),
        }

    t0 = time.perf_counter()
    conc16 = run_concurrency("bf16")  # first arm eats the compiles
    compile_s = time.perf_counter() - t0
    conc8 = run_concurrency("int8")
    cache16 = run_cache("bf16")
    cache8 = run_cache("int8")
    # in-bench accuracy gate: greedy tokens must MATCH bf16 on the
    # short-horizon sanity workload (first SANITY_AGREE generated tokens
    # of every request).  Beyond it, random-INIT logits sit within
    # quantization noise of each other (near-tied argmax margins a
    # trained model does not have — docs/guide/quantization.md
    # "Accuracy gates"), so the full-horizon agreement fraction is
    # reported as telemetry, not asserted.
    SANITY_AGREE = 4
    toks16, toks8 = conc16.pop("tokens"), conc8.pop("tokens")
    short_ok = all(a[:prompt + SANITY_AGREE] == b[:prompt + SANITY_AGREE]
                   for a, b in zip(toks16, toks8))
    assert short_ok, (
        "int8 greedy tokens diverged from bf16 within the sanity horizon")
    full_match = sum(a == b for a, b in zip(toks16, toks8)) / len(toks16)
    ratio = conc8["peak_concurrent_slots"] / max(
        conc16["peak_concurrent_slots"], 1)
    return {
        "slot_ratio": round(ratio, 2),
        "capacity_ok": (ratio >= 2.0
                        and cache8["hit_rate"] >= cache16["hit_rate"]),
        "greedy_match": short_ok,
        "greedy_match_tokens": SANITY_AGREE,
        "full_horizon_match_fraction": round(full_match, 3),
        "pool_budget_bytes": budget,
        "page_ratio": round(conc8["num_pages"] / conc16["num_pages"], 3),
        "hit_rate_bf16": cache16["hit_rate"],
        "hit_rate_int8": cache8["hit_rate"],
        "hit_rate_gain": round(cache8["hit_rate"] - cache16["hit_rate"], 4),
        "compile_time_s": round(compile_s, 1),
        "step_time_s": round(conc8["wall_s"] / max(conc8["ticks"], 1), 6),
        "n_requests": n_requests,
        "ref_slots": ref_slots,
        "prompt_len": prompt,
        "gen_len": gen,
        "groups": groups,
        "per_group": per_group,
        "shared_len": shared_len,
        "rows": [conc16, conc8, cache16, cache8],
    }


def bench_engine(cfg, params, concurrency: int, prompt: int, gen: int,
                 vocab: int, reps: int) -> dict:
    """Engine throughput at one occupancy level vs the sequential path."""
    import jax
    import numpy as np

    from megatron_llm_tpu.generation import generate_tokens

    prompts = _requests(concurrency, prompt, gen, vocab)

    def run_engine():
        eng = make_engine(cfg, params, max_slots=max(concurrency, 1),
                          max_seq=prompt + gen)
        run_workload(eng, [(p, gen, dict(GREEDY_KW)) for p in prompts])
        return eng

    # warm the compile caches (prefill bucket + tick), then time
    run_engine()
    best = float("inf")
    ticks = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        eng = run_engine()
        dt = time.perf_counter() - t0
        if dt < best:
            best, ticks = dt, eng.ticks

    # sequential baseline: one dense generate_tokens call per request
    # (compile once on the first call, timing from the second rep)
    S = prompt + gen
    def run_sequential():
        for p in prompts:
            tokens = np.zeros((1, S), np.int32)
            tokens[0, :prompt] = p
            r = generate_tokens(
                cfg, params, tokens, np.asarray([prompt], np.int32), S,
                prefill_len=prompt, termination_id=0,
                sample_key=jax.random.PRNGKey(0), top_k=1,
                use_eod_for_termination=False)
            jax.block_until_ready(r.tokens)

    run_sequential()
    seq_best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run_sequential()
        seq_best = min(seq_best, time.perf_counter() - t0)

    total_tokens = concurrency * gen
    return {
        "concurrency": concurrency,
        "prompt_len": prompt,
        "gen_len": gen,
        "engine_s": round(best, 4),
        "engine_tok_s": round(total_tokens / best, 1),
        "tick_ms": round(best / max(ticks, 1) * 1e3, 3),
        "ticks": ticks,
        "sequential_s": round(seq_best, 4),
        "sequential_tok_s": round(total_tokens / seq_best, 1),
        "speedup_vs_sequential": round(seq_best / best, 2),
    }


def bench_shared_prefix(cfg, params, concurrency: int, shared_len: int,
                        tail_len: int, gen: int, vocab: int) -> dict:
    """Warm-cache shared-prefix workload, prefix cache on vs off."""
    import time

    import numpy as np

    rng = np.random.default_rng(1)
    shared = [int(t) for t in rng.integers(1, vocab, shared_len)]
    tails = [[int(t) for t in rng.integers(1, vocab, tail_len)]
             for _ in range(concurrency)]

    def run(prefix_cache: bool) -> dict:
        eng = make_engine(cfg, params, max_slots=concurrency,
                          max_seq=shared_len + tail_len + gen,
                          prefix_cache=prefix_cache)
        # warm the cache (and the compile caches) with one full request
        run_workload(eng, [(shared + tails[0], gen, dict(GREEDY_KW))])
        pt0 = eng.prefill_tokens_computed
        hit0, miss0 = eng.prefix_hit_tokens, eng.prefix_miss_tokens
        t0 = time.perf_counter()
        reqs = run_workload(
            eng, [(shared + t, gen, dict(GREEDY_KW)) for t in tails])
        wall = time.perf_counter() - t0
        ttfts = [r.ttft for r in reqs]
        hit = eng.prefix_hit_tokens - hit0
        miss = eng.prefix_miss_tokens - miss0
        return {
            "prefix_cache": prefix_cache,
            "prefill_tokens_computed": eng.prefill_tokens_computed - pt0,
            "hit_rate": round(hit / max(hit + miss, 1), 4),
            "ttft_mean_ms": round(1e3 * sum(ttfts) / len(ttfts), 2),
            "ttft_max_ms": round(1e3 * max(ttfts), 2),
            "wall_s": round(wall, 4),
            "decode_tok_s": round(concurrency * gen / wall, 1),
            "pages_cached": len(eng.pool.cached),
            "cow_copies": eng.cow_copies,
        }

    # compile-warm both arms' chunk shapes, then measure fresh engines
    run(False)
    run(True)
    off = run(False)
    on = run(True)
    reduction = (off["prefill_tokens_computed"]
                 / max(on["prefill_tokens_computed"], 1))
    return {
        "concurrency": concurrency,
        "shared_len": shared_len,
        "tail_len": tail_len,
        "gen_len": gen,
        "prefill_token_reduction": round(reduction, 2),
        "ttft_mean_speedup": round(
            off["ttft_mean_ms"] / max(on["ttft_mean_ms"], 1e-9), 2),
        "reduction_ok": reduction >= 2.0,
        "cache_on": on,
        "cache_off": off,
    }


def _percentile(xs, q):
    import numpy as np

    return float(np.percentile(np.asarray(xs, np.float64), q))


def bench_slo(cfg, params, slots: int, n_hi: int, n_lo: int,
              prompt_len: int, gen_hi: int, gen_lo: int, vocab: int,
              ttft_slo_ms: float) -> dict:
    """Mixed-priority overload through each scheduling policy.

    Batch traffic (priority 2, ``gen_lo`` tokens, no deadline) is
    submitted first and driven until every slot is decoding — the
    overload steady state — then the interactive burst (priority 0,
    ``gen_hi`` tokens, ``ttft_slo_ms`` TTFT deadline) arrives.  fcfs
    makes the burst wait behind the whole batch backlog; priority/slo
    reorder admission and preempt batch decoders by page release, so the
    burst's TTFT stops scaling with the backlog."""
    import time

    import numpy as np

    from megatron_llm_tpu.generation import RequestShed

    rng = np.random.default_rng(7)
    lo_prompts = [[int(t) for t in rng.integers(1, vocab, prompt_len)]
                  for _ in range(n_lo)]
    hi_prompts = [[int(t) for t in rng.integers(1, vocab, prompt_len)]
                  for _ in range(n_hi)]
    kw = dict(GREEDY_KW)

    def run(policy: str) -> dict:
        eng = make_engine(cfg, params, max_slots=slots,
                          max_seq=prompt_len + max(gen_hi, gen_lo),
                          sched_policy=policy)
        lo = [eng.submit(p, gen_lo, priority=2, seed=i, **kw)
              for i, p in enumerate(lo_prompts)]
        # drive until every slot decodes batch traffic (true overload)
        while sum(r._t_first > 0 for r in lo) < min(slots, n_lo):
            eng.step()
        hi = [eng.submit(p, gen_hi, priority=0,
                         ttft_deadline_ms=ttft_slo_ms, seed=100 + i, **kw)
              for i, p in enumerate(hi_prompts)]
        t0 = time.perf_counter()
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        ticks = max(eng.ticks, 1)
        shed = 0
        for r in hi + lo:
            try:
                r.result(timeout=600)
            except RequestShed:
                shed += 1

        def klass(reqs, deadline_ms):
            ttfts = [r.ttft for r in reqs if r.ttft is not None]
            missed = sum(
                1 for r in reqs
                if r.shed or (deadline_ms is not None and r.ttft is not None
                              and r.ttft > deadline_ms / 1e3))
            return {
                "n": len(reqs),
                "ttft_p50_ms": round(1e3 * _percentile(ttfts, 50), 2),
                "ttft_p99_ms": round(1e3 * _percentile(ttfts, 99), 2),
                "deadline_miss_rate": round(missed / max(len(reqs), 1), 4),
            }

        return {
            "policy": policy,
            "hi": klass(hi, ttft_slo_ms),
            "lo": klass(lo, None),
            "preemptions": eng.preemptions,
            "shed": eng.shed_requests,
            "wall_s": round(wall, 4),
            "tick_ms": round(wall / ticks * 1e3, 3),
        }

    # compile-warm every shape on a throwaway arm, then measure
    t0 = time.perf_counter()
    run("fcfs")
    compile_s = time.perf_counter() - t0
    rows = [run(p) for p in ("fcfs", "priority", "slo")]
    by = {r["policy"]: r for r in rows}
    speedup = (by["fcfs"]["hi"]["ttft_p99_ms"]
               / max(by["slo"]["hi"]["ttft_p99_ms"], 1e-9))
    return {
        "slots": slots,
        "n_hi": n_hi,
        "n_lo": n_lo,
        "prompt_len": prompt_len,
        "gen_hi": gen_hi,
        "gen_lo": gen_lo,
        "ttft_slo_ms": ttft_slo_ms,
        "hi_p99_ttft_speedup": round(speedup, 2),
        "speedup_ok": speedup >= 2.0,
        "compile_time_s": round(compile_s, 1),
        "step_time_s": by["fcfs"]["tick_ms"] / 1e3,
        "rows": rows,
    }


def bench_spec(cfg, params, draft, levels, prompt, gen, vocab,
               spec_k: int, reps: int) -> dict:
    """Speculative decoding on/off on identical greedy traffic per level.

    Both arms run the SAME prompts through engines sharing compiled
    programs; the on-arm's emitted tokens are asserted equal to the
    off-arm's (the losslessness contract, cheap to re-check here)."""
    import numpy as np

    def run(c: int, spec_on: bool) -> dict:
        prompts = _requests(c, prompt, gen, vocab, seed=11)
        ekw = dict(max_slots=c, max_seq=prompt + gen)
        if spec_on:
            ekw.update(spec_k=spec_k, spec_draft=draft, spec_adaptive=False)
        best = None
        for _ in range(max(reps, 1) + 1):  # first rep warms the compiles
            eng = make_engine(cfg, params, **ekw)
            t0 = time.perf_counter()
            reqs = run_workload(
                eng, [(p, gen, dict(GREEDY_KW)) for p in prompts])
            wall = time.perf_counter() - t0
            if best is None or wall < best[0]:
                best = (wall, eng, reqs)
        wall, eng, reqs = best
        lat_ms = sorted(1e3 * r.latency for r in reqs)
        row = {
            "spec": spec_on,
            "wall_s": round(wall, 4),
            "decode_tok_s": round(c * gen / wall, 1),
            "ticks": eng.ticks,
            "tok_per_tick": round(eng.ticked_tokens / max(eng.ticks, 1), 3),
            "latency_p50_ms": round(_percentile(lat_ms, 50), 2),
            "latency_p99_ms": round(_percentile(lat_ms, 99), 2),
        }
        if spec_on:
            stats = eng.spec_stats()
            row["acceptance_rate"] = stats["acceptance_rate"]
        row["_tokens"] = [r.generated for r in reqs]
        return row

    # compile-warm both arms' programs on a throwaway pass, timed for the
    # bench-contract budget fields
    t0 = time.perf_counter()
    run(levels[0], False)
    run(levels[0], True)
    compile_s = time.perf_counter() - t0

    rows = []
    for c in levels:
        off = run(c, False)
        on = run(c, True)
        assert on.pop("_tokens") == off.pop("_tokens"), (
            "speculative decode emitted different tokens — losslessness "
            "violated")
        rows.append({
            "concurrency": c,
            "speedup": round(on["decode_tok_s"]
                             / max(off["decode_tok_s"], 1e-9), 2),
            "on": on,
            "off": off,
        })
    by_c = {r["concurrency"]: r for r in rows}
    headline = by_c.get(1, rows[0])
    return {
        "prompt_len": prompt,
        "gen_len": gen,
        "spec_k": spec_k,
        "speedup_c1": headline["speedup"],
        "speedup_ok": headline["speedup"] >= 1.3,
        "acceptance_rate": headline["on"]["acceptance_rate"],
        "compile_time_s": round(compile_s, 1),
        "step_time_s": round(
            headline["on"]["wall_s"] / max(headline["on"]["ticks"], 1), 6),
        "rows": rows,
    }


class _CharTok:
    """Deterministic char-level tokenizer for the router fleet (the wire
    carries text; 1 char == 1 token keeps prefix lengths exact)."""

    eod = 0
    bos = 1

    def __init__(self, vocab: int):
        self._n = vocab

    @property
    def vocab_size(self):
        return self._n

    def tokenize(self, text):
        return [2 + (ord(c) % (self._n - 2)) for c in text]

    def detokenize(self, ids):
        return "".join(chr(97 + (int(i) % 26)) for i in ids if i >= 2)


def bench_router(cfg, params, n_replicas: int, groups: int, per_group: int,
                 shared_len: int, tail_len: int, gen: int, vocab: int,
                 slots: int, client_concurrency: int = 4) -> dict:
    """Fleet shared-prefix workload: prefix_affinity vs round_robin, then
    a mid-run replica kill under the affinity arm (see module doc)."""
    import random
    import string
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.observability.registry import get_registry
    from megatron_llm_tpu.serving.router.server import RouterServer

    rng = random.Random(3)
    letters = string.ascii_letters + string.digits
    shareds = ["".join(rng.choice(letters) for _ in range(shared_len))
               for _ in range(groups)]
    tails = [["".join(rng.choice(letters) for _ in range(tail_len))
              for _ in range(per_group)] for _ in range(groups)]
    gen_kw = {"tokens_to_generate": gen, "top_k": 1}

    def put(base_url: str, prompt: str):
        req = urllib.request.Request(
            base_url + "/api",
            data=json.dumps({"prompts": [prompt], **gen_kw}).encode(),
            headers={"Content-Type": "application/json"}, method="PUT")
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                resp.read()
                code = resp.status
        except urllib.error.HTTPError as e:
            e.read()
            code = e.code
        except urllib.error.URLError:
            code = 0
        return code, time.perf_counter() - t0

    # the pool must be able to hold several groups' cached prefixes PLUS
    # the active slots' commitments, or LRU eviction silently turns the
    # workload into a cache-thrash benchmark (page_size from cfg.inference)
    ps = cfg.inference.page_size
    pages_per_seq = -(-(shared_len + tail_len + gen + 1) // ps)
    pool_pages = (groups + slots) * (pages_per_seq + 1) + 16

    def spawn_fleet(policy: str):
        engines, servers, urls = [], [], []
        for _ in range(n_replicas):
            eng = make_engine(cfg, params, tokenizer=_CharTok(vocab),
                              max_slots=slots, num_pages=pool_pages,
                              max_seq=shared_len + tail_len + gen + 1)
            srv = MegatronServer(eng)
            port = srv.start_background(port=0)  # ephemeral: no port races
            engines.append(eng)
            servers.append(srv)
            urls.append(f"http://127.0.0.1:{port}")
        kwargs = (dict(prefix_chars=shared_len)
                  if policy == "prefix_affinity" else {})
        router = RouterServer(urls, policy=policy, policy_kwargs=kwargs,
                              poll_interval=0.25, forward_timeout_s=600.0)
        rport = router.start_background()
        return engines, servers, urls, router, f"http://127.0.0.1:{rport}"

    def run_arm(policy: str) -> dict:
        engines, servers, urls, router, base = spawn_fleet(policy)
        try:
            # warm: one request per group (compiles + seeds each group's
            # prefix wherever this policy lands it — same procedure both
            # arms, so neither gets a head start)
            t0 = time.perf_counter()
            for g in range(groups):
                code, _ = put(base, shareds[g] + tails[g][0])
                assert code == 200, f"warm request failed: {code}"
            warm_s = time.perf_counter() - t0
            hit0 = sum(e.prefix_hit_tokens for e in engines)
            miss0 = sum(e.prefix_miss_tokens for e in engines)
            pre0 = sum(e.prefill_tokens_computed for e in engines)
            ticks0 = sum(e.ticks for e in engines)
            jobs = [(shareds[g] + tails[g][r])
                    for r in range(1, per_group)
                    for g in range(groups)]
            # deterministic shuffle: real arrivals are not group-aligned,
            # and an interleave that happens to alternate groups in fleet
            # parity would hand round_robin accidental affinity
            random.Random(11).shuffle(jobs)
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=client_concurrency) as ex:
                results = list(ex.map(lambda p: put(base, p), jobs))
            wall = time.perf_counter() - t0
            assert all(c == 200 for c, _ in results), (
                f"measured-phase failures: {[c for c, _ in results]}")
            lat = sorted(t for _, t in results)
            hit = sum(e.prefix_hit_tokens for e in engines) - hit0
            miss = sum(e.prefix_miss_tokens for e in engines) - miss0
            ticks = sum(e.ticks for e in engines) - ticks0
            arm = {
                "policy": policy,
                "n_requests": len(jobs),
                "fleet_hit_rate": round(hit / max(hit + miss, 1), 4),
                "prefill_tokens_computed":
                    sum(e.prefill_tokens_computed for e in engines) - pre0,
                "ttft_mean_ms": round(1e3 * sum(lat) / len(lat), 2),
                "ttft_p99_ms": round(1e3 * _percentile(lat, 99), 2),
                "wall_s": round(wall, 4),
                "decode_tok_s": round(len(jobs) * gen / wall, 1),
                "warm_s": round(warm_s, 2),
                "ticks": ticks,
                "per_replica_ticks": [e.ticks for e in engines],
            }
            if policy != "prefix_affinity":
                return arm
            # ---- failover: kill the busiest replica mid-run -------------
            victim = max(range(n_replicas),
                         key=lambda i: engines[i].ticks)
            reg = get_registry()
            fo0 = reg.counter("mlt_router_failovers_total").value
            servers[victim].stop()  # socket closed: connects now refused
            fo_jobs = [(shareds[g] + tails[g][0] + "X")
                       for g in range(groups) for _ in range(2)]
            with ThreadPoolExecutor(max_workers=client_concurrency) as ex:
                fo_results = list(ex.map(lambda p: put(base, p), fo_jobs))
            dropped = sum(c != 200 for c, _ in fo_results)
            arm["failover"] = {
                "killed": urls[victim],
                "requests": len(fo_jobs),
                "dropped": dropped,
                "failovers": int(
                    reg.counter("mlt_router_failovers_total").value - fo0),
                "killed_state": router.registry.get(urls[victim]).state,
                "ok": dropped == 0,
            }
            return arm
        finally:
            router.stop()
            for srv in servers:
                try:
                    srv.stop()
                except Exception:
                    pass

    t0 = time.perf_counter()
    rr = run_arm("round_robin")  # first arm also eats the compiles
    compile_s = time.perf_counter() - t0
    aff = run_arm("prefix_affinity")
    speedup = rr["ttft_mean_ms"] / max(aff["ttft_mean_ms"], 1e-9)
    hit_gain = aff["fleet_hit_rate"] - rr["fleet_hit_rate"]
    return {
        "n_replicas": n_replicas,
        "groups": groups,
        "per_group": per_group,
        "shared_len": shared_len,
        "tail_len": tail_len,
        "gen_len": gen,
        "ttft_mean_speedup": round(speedup, 2),
        "fleet_hit_rate_gain": round(hit_gain, 4),
        "speedup_ok": (speedup >= 1.05 and hit_gain > 0
                       and aff["failover"]["ok"]),
        "failover": aff["failover"],
        "compile_time_s": round(compile_s, 1),
        "step_time_s": round(aff["wall_s"] / max(aff["ticks"], 1), 6),
        "rows": [rr, aff],
    }


def bench_streaming(cfg, params, n_replicas: int, concurrency: int,
                    prompt_len: int, gen: int, vocab: int, slots: int,
                    burst: int) -> dict:
    """Streaming serving tier (ISSUE 18): client-observed TTFT streamed
    vs buffered through a real 2-replica fleet + router, plus the
    router admission-queue arm.

    Section 1 (first-token honesty): ``concurrency`` concurrent clients
    stream through the router; each client's time-to-first-body-byte is
    compared against the replica's own ``X-MLT-TTFT-S`` stamp riding
    the response headers.  Gate: streamed client TTFT within 1.2x of
    the stamp (+ a small absolute loopback slack) — the stamp, the
    headers, and the first flushed byte describe the same instant.  The
    SAME payloads run buffered: there the first body byte IS the whole
    response, so buffered first-byte ~= total latency, and the headline
    is how much earlier streaming delivers the first token.  An
    in-bench identity assert pins the streamed terminal ``done`` body
    byte-equal to the buffered body on the same seeded request.

    Section 2 (admission queue): a ``burst``-client saturation burst
    against a deliberately tiny fleet (1 slot + 1-deep engine queue per
    replica).  The baseline router (no admission queue, no proxy
    retries) surfaces replica 503s to clients; the admission-queue
    router holds arrivals in its bounded FIFO and drops nothing."""
    import http.client
    import random
    import string
    from concurrent.futures import ThreadPoolExecutor
    from urllib.parse import urlparse

    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.serving.router.server import RouterServer
    from megatron_llm_tpu.serving.streaming import parse_sse

    rng = random.Random(13)
    letters = string.ascii_letters + string.digits

    def prompt():
        return "".join(rng.choice(letters) for _ in range(prompt_len))

    def client_put(base: str, payload: dict):
        """PUT via http.client with incremental reads: returns (status,
        headers, raw_body, t_first_body_byte_s, t_total_s)."""
        u = urlparse(base)
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=600)
        t0 = time.perf_counter()
        conn.request("PUT", "/api", body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        hdrs = dict(resp.getheaders())
        raw, t_first = b"", None
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            if t_first is None:
                t_first = time.perf_counter() - t0
            raw += chunk
        t_total = time.perf_counter() - t0
        conn.close()
        return resp.status, hdrs, raw, t_first, t_total

    def spawn_fleet(*, fleet_slots: int, max_queue=None, admission=False):
        servers, urls = [], []
        for _ in range(n_replicas):
            ekw = dict(max_slots=fleet_slots,
                       max_seq=prompt_len + gen + 1)
            if max_queue is not None:
                ekw["max_queue"] = max_queue
            eng = make_engine(cfg, params, tokenizer=_CharTok(vocab),
                              **ekw)
            srv = MegatronServer(eng)
            port = srv.start_background(port=0)
            servers.append(srv)
            urls.append(f"http://127.0.0.1:{port}")
        rkw = dict(policy="round_robin", poll_interval=0.25,
                   forward_timeout_s=600.0)
        if admission:
            # limit = fleet decode capacity, so replicas never even see
            # the overflow; deep-enough FIFO that the burst fits
            rkw.update(max_retries=0, admission_depth=max(burst, 8),
                       admission_limit=n_replicas * fleet_slots,
                       admission_timeout_s=600.0)
        else:
            rkw.update(max_retries=0)
        router = RouterServer(urls, **rkw)
        rport = router.start_background()
        return servers, router, f"http://127.0.0.1:{rport}"

    gen_kw = {"tokens_to_generate": gen, "top_k": 1, "random_seed": 3}

    # ---- section 1: streamed vs buffered TTFT at `concurrency` ----------
    servers, router, base = spawn_fleet(fleet_slots=slots)
    try:
        # warm both write paths (compiles ride the first requests)
        t0 = time.perf_counter()
        code, _, _, _, _ = client_put(base, {"prompts": [prompt()],
                                             **gen_kw})
        assert code == 200, f"warm buffered request failed: {code}"
        code, _, _, _, _ = client_put(base, {"prompts": [prompt()],
                                             **gen_kw, "stream": True})
        assert code == 200, f"warm streamed request failed: {code}"
        compile_s = time.perf_counter() - t0

        # identity probe: the streamed done body == the buffered body
        probe = {"prompts": [prompt()], **gen_kw, "logprobs": True}
        code, _, braw, _, _ = client_put(base, probe)
        assert code == 200
        buffered_body = json.loads(braw)
        buffered_body.pop("timing", None)
        code, _, sraw, _, _ = client_put(base, {**probe, "stream": True})
        assert code == 200
        frames = parse_sse(sraw)
        assert frames[-1][0] == "done", f"stream ended with {frames[-1][0]}"
        done = frames[-1][1]
        done.pop("timing", None)
        assert done == buffered_body, (
            "streamed terminal body diverged from the buffered response")

        prompts = [prompt() for _ in range(concurrency)]

        def measure(stream: bool):
            def one(p):
                payload = {"prompts": [p], **gen_kw}
                if stream:
                    payload["stream"] = True
                code, hdrs, _, t_first, t_total = client_put(base, payload)
                assert code == 200, f"request failed: {code}"
                stamp = hdrs.get("X-MLT-TTFT-S")
                return (t_first, t_total,
                        float(stamp) if stamp is not None else None)
            with ThreadPoolExecutor(max_workers=concurrency) as ex:
                return list(ex.map(one, prompts))

        streamed = measure(stream=True)
        buffered = measure(stream=False)
    finally:
        router.stop()
        for srv in servers:
            try:
                srv.stop()
            except Exception:
                pass

    s_ttft = [t for t, _, _ in streamed]
    s_total = [t for _, t, _ in streamed]
    stamps = [s for _, _, s in streamed if s is not None]
    b_ttfb = [t for t, _, _ in buffered]
    b_total = [t for _, t, _ in buffered]
    mean = lambda xs: sum(xs) / max(len(xs), 1)  # noqa: E731
    # the honesty gate: the client sees the first byte when the stamp
    # says the first token existed (1.2x + loopback/GIL slack)
    stamp_ratio = mean(s_ttft) / max(mean(stamps), 1e-9)
    stamp_ok = mean(s_ttft) <= 1.2 * mean(stamps) + 0.25
    # buffered responses deliver nothing until everything: first byte
    # lands with the full body
    buffered_is_total = mean(b_ttfb) >= 0.9 * mean(b_total)
    first_token_speedup = mean(b_ttfb) / max(mean(s_ttft), 1e-9)
    stream_rows = [
        {"arm": "streamed",
         "client_ttft_mean_ms": round(1e3 * mean(s_ttft), 2),
         "client_ttft_p99_ms": round(1e3 * _percentile(s_ttft, 99), 2),
         "replica_stamp_mean_ms": round(1e3 * mean(stamps), 2),
         "total_mean_ms": round(1e3 * mean(s_total), 2),
         "stamped": len(stamps)},
        {"arm": "buffered",
         "client_ttft_mean_ms": round(1e3 * mean(b_ttfb), 2),
         "client_ttft_p99_ms": round(1e3 * _percentile(b_ttfb, 99), 2),
         "total_mean_ms": round(1e3 * mean(b_total), 2)},
    ]

    # ---- section 2: admission queue absorbs a saturation burst ----------
    def run_burst(admission: bool) -> dict:
        servers, router, base = spawn_fleet(fleet_slots=1, max_queue=1,
                                            admission=admission)
        try:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=burst) as ex:
                codes = list(ex.map(
                    lambda p: client_put(base, {"prompts": [p],
                                                **gen_kw})[0],
                    [prompt() for _ in range(burst)]))
            wall = time.perf_counter() - t0
            row = {
                "admission_queue": admission,
                "requests": burst,
                "ok": sum(c == 200 for c in codes),
                "dropped": sum(c != 200 for c in codes),
                "wall_s": round(wall, 4),
            }
            if admission:
                row["admission_stats"] = router.admission.stats()
            return row
        finally:
            router.stop()
            for srv in servers:
                try:
                    srv.stop()
                except Exception:
                    pass

    baseline = run_burst(admission=False)
    gated = run_burst(admission=True)

    return {
        "n_replicas": n_replicas,
        "concurrency": concurrency,
        "prompt_len": prompt_len,
        "gen_len": gen,
        "slots": slots,
        "burst": burst,
        "first_token_speedup": round(first_token_speedup, 2),
        "stamp_ratio": round(stamp_ratio, 3),
        "stamp_ok": stamp_ok,
        "buffered_first_byte_is_total": buffered_is_total,
        "identity_ok": True,  # asserted above
        "baseline_dropped": baseline["dropped"],
        "admission_dropped": gated["dropped"],
        "stream_ok": (stamp_ok and buffered_is_total
                      and first_token_speedup >= 1.0
                      and baseline["dropped"] > 0
                      and gated["dropped"] == 0),
        "compile_time_s": round(compile_s, 1),
        "step_time_s": round(mean(s_total) / max(gen, 1), 6),
        "rows": stream_rows + [baseline, gated],
    }


def bench_disagg(cfg, params, prompt_short: int, gen_short: int,
                 prompt_long: int, gen_long: int, n_short: int,
                 n_long: int, short_reqs: int, long_reqs: int, vocab: int,
                 slots: int, long_prompt_chars: int) -> dict:
    """Disaggregated prefill/decode (ISSUE 19, serving/handoff/): a mixed
    workload — a saturated short-prompt decode class + a long-prompt
    prefill class — through two fleets at EQUAL chip count:

    * **unified**: 2 unified replicas behind the ``disagg`` router
      (role-less fleet, so the policy degrades to least_loaded — the
      pre-disagg baseline).  Long prefill chunks share each replica's
      tick stream with the decode batch, so every long arrival stretches
      the decode class's inter-token times.
    * **split**: 1 prefill-role + 1 decode-role replica behind the same
      router.  Long prompts go prefill→KV push→decode; the decode
      replica sees them trie-hot (prefill collapses to the refeed
      token), so its tick stream stays pure decode.

    Per class: client latency, server-stamped TTFT, and decode-class
    TPOT ((latency - ttft) / (gen - 1)) from each replica's own flight
    timing.  The in-bench identity assert pins every request's text
    byte-equal across arms — the handoff is lossless, not approximate.
    Headline: decode-class p99 TPOT speedup, split over unified.
    Gate: > 1x (decode isolation must actually protect the decode
    class) with all texts identical and every long split request
    actually handed off."""
    import random
    import string
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from megatron_llm_tpu.generation.server import MegatronServer
    from megatron_llm_tpu.serving.router.server import RouterServer

    rng = random.Random(11)
    letters = string.ascii_letters + string.digits

    def text(n):
        return "".join(rng.choice(letters) for _ in range(n))

    # distinct prompts everywhere: prefix-cache hits would let the
    # unified arm skip prefill work the split arm is designed to absorb
    shorts = [[text(prompt_short) for _ in range(short_reqs)]
              for _ in range(n_short)]
    longs = [[text(prompt_long) for _ in range(long_reqs)]
             for _ in range(n_long)]

    ps = cfg.inference.page_size
    pages_per_seq = -(-(prompt_long + max(gen_short, gen_long) + 1) // ps)
    pool_pages = (slots + n_long * long_reqs + 2) * (pages_per_seq + 1) + 16
    max_seq = prompt_long + max(gen_short, gen_long) + 1

    def put(base_url: str, prompt: str, gen: int):
        req = urllib.request.Request(
            base_url + "/api",
            data=json.dumps({"prompts": [prompt],
                             "tokens_to_generate": gen,
                             "top_k": 1, "random_seed": 5}).encode(),
            headers={"Content-Type": "application/json"}, method="PUT")
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=600) as resp:
            body = json.loads(resp.read())
            code = resp.status
        wall = time.perf_counter() - t0
        assert code == 200, f"request failed: {code} {body}"
        t = body.get("timing") or {}
        return {"text": body["text"][0], "wall_s": wall,
                "ttft_s": t.get("ttft_s"), "latency_s": t.get("latency_s")}

    def spawn_fleet(roles):
        servers, urls = [], []
        for role in roles:
            eng = make_engine(cfg, params, tokenizer=_CharTok(vocab),
                              max_slots=slots, num_pages=pool_pages,
                              max_seq=max_seq)
            srv = MegatronServer(eng, role=role)
            port = srv.start_background(port=0)
            servers.append(srv)
            urls.append(f"http://127.0.0.1:{port}")
        router = RouterServer(
            urls, policy="disagg",
            policy_kwargs={"long_prompt_chars": long_prompt_chars},
            poll_interval=0.25, forward_timeout_s=600.0)
        rport = router.start_background()
        return servers, router, f"http://127.0.0.1:{rport}"

    def run_arm(roles) -> dict:
        servers, router, base = spawn_fleet(roles)
        try:
            # warm both request shapes (compiles ride the first ones)
            t0 = time.perf_counter()
            put(base, text(prompt_short), gen_short)
            put(base, text(prompt_long), gen_long)
            compile_s = time.perf_counter() - t0

            def short_client(i):
                return [put(base, p, gen_short) for p in shorts[i]]

            def long_client(i):
                return [put(base, p, gen_long) for p in longs[i]]

            with ThreadPoolExecutor(max_workers=n_short + n_long) as ex:
                sf = [ex.submit(short_client, i) for i in range(n_short)]
                lf = [ex.submit(long_client, i) for i in range(n_long)]
                srows = [r for f in sf for r in f.result()]
                lrows = [r for f in lf for r in f.result()]
            handoffs = router._handoffs.value
            handoff_failures = router._handoff_failures.value
        finally:
            router.stop()
            for srv in servers:
                try:
                    srv.stop()
                except Exception:
                    pass

        def klass(rows, gen):
            ttfts = [r["ttft_s"] for r in rows if r["ttft_s"] is not None]
            tpots = [(r["latency_s"] - r["ttft_s"]) / max(gen - 1, 1)
                     for r in rows
                     if r["ttft_s"] is not None
                     and r["latency_s"] is not None]
            walls = [r["wall_s"] for r in rows]
            return {
                "requests": len(rows),
                "ttft_mean_ms": round(1e3 * sum(ttfts)
                                      / max(len(ttfts), 1), 2),
                "ttft_p99_ms": round(1e3 * _percentile(ttfts, 99), 2),
                "tpot_mean_ms": round(1e3 * sum(tpots)
                                      / max(len(tpots), 1), 3),
                "tpot_p99_ms": round(1e3 * _percentile(tpots, 99), 3),
                "client_latency_mean_ms": round(
                    1e3 * sum(walls) / max(len(walls), 1), 2),
                "_tpots": tpots,
            }

        return {
            "arm": "+".join(roles),
            "short": klass(srows, gen_short),
            "long": klass(lrows, gen_long),
            "handoffs": handoffs,
            "handoff_failures": handoff_failures,
            "compile_time_s": compile_s,
            "_texts": ([r["text"] for r in srows]
                       + [r["text"] for r in lrows]),
        }

    unified = run_arm(("unified", "unified"))
    split = run_arm(("prefill", "decode"))

    # losslessness: the handoff path must not change a single token
    assert unified["_texts"] == split["_texts"], (
        "disagg texts diverged from the unified fleet")
    # the split arm must actually have migrated every long request
    n_long_total = n_long * long_reqs + 1  # + the long warm-up request
    assert split["handoffs"] >= n_long_total, (
        f"only {split['handoffs']} handoffs for {n_long_total} long "
        f"requests")
    assert unified["handoffs"] == 0, "role-less fleet must never hand off"

    u99 = unified["short"]["tpot_p99_ms"]
    s99 = split["short"]["tpot_p99_ms"]
    tpot_speedup = u99 / max(s99, 1e-9)
    rows = []
    for arm in (unified, split):
        for klass_name in ("short", "long"):
            k = dict(arm[klass_name])
            k.pop("_tpots", None)
            rows.append({"arm": arm["arm"], "class": klass_name, **k})
    return {
        "n_replicas": 2,
        "slots": slots,
        "prompt_short": prompt_short, "gen_short": gen_short,
        "prompt_long": prompt_long, "gen_long": gen_long,
        "n_short": n_short, "n_long": n_long,
        "short_reqs": short_reqs, "long_reqs": long_reqs,
        "long_prompt_chars": long_prompt_chars,
        "decode_tpot_p99_speedup": round(tpot_speedup, 3),
        "decode_tpot_mean_speedup": round(
            unified["short"]["tpot_mean_ms"]
            / max(split["short"]["tpot_mean_ms"], 1e-9), 3),
        "long_ttft_mean_ms": {
            "unified": unified["long"]["ttft_mean_ms"],
            "split": split["long"]["ttft_mean_ms"]},
        "handoffs": split["handoffs"],
        "handoff_failures": split["handoff_failures"],
        "identity_ok": True,  # asserted above
        "disagg_ok": (tpot_speedup > 1.0
                      and split["handoff_failures"] == 0),
        "compile_time_s": round(unified["compile_time_s"]
                                + split["compile_time_s"], 1),
        "step_time_s": round(
            split["short"]["tpot_mean_ms"] / 1e3, 6),
        "rows": rows,
    }


def bench_pp(cfg, params, pps, concurrency: int, prompt: int, gen: int,
             vocab: int, reps: int) -> dict:
    """Pipeline-parallel serving tick (ISSUE 20, parallel/pp_serve.py):
    the same greedy decode workload through three engine layouts at
    EQUAL chip count per comparison:

    * **pp=1** (tp=N, pp=1): the tp-only engine on N chips — the
      pre-pp baseline whose executables a pp engine must never reuse.
    * **pp=N** (tp=1, pp=N): N pipeline stages, each holding L/N layers
      of params AND KV pool, ragged rows microbatched through the stage
      scan with the boundary ppermutes riding between adjacent GEMMs.

    A flat single-chip arm runs first as the token-identity reference.
    In-bench gates:
    greedy tokens identical across ALL arms (log-probs within 5e-6),
    per-stage KV bytes exactly kv_pool_bytes/pp, and the stage-permute
    mechanism machine-asserted in the compiled tick HLO — the ppermute
    chain under the ``stage-permute`` scope, not assumed.  Headline:
    decode tok/s of the largest pp arm over its equal-chip pp=1 arm
    (gate: >= 0.85, i.e. pipelining the tick costs < 15% decode
    throughput while cutting per-chip KV residency to 1/pp)."""
    import copy

    import jax
    import numpy as np

    from megatron_llm_tpu.core.parallel_state import build_mesh
    from megatron_llm_tpu.parallel import pp_serve as pp_serve_mod

    prompts = _requests(concurrency, prompt, gen, vocab)

    def run_arm(pp, tp):
        devs = jax.devices()
        mesh = (None if pp * tp == 1 else build_mesh(
            tensor_model_parallel_size=tp,
            pipeline_model_parallel_size=pp,
            data_parallel_size=1, devices=devs[:pp * tp]))

        def once():
            eng = make_engine(copy.deepcopy(cfg), params,
                              max_slots=concurrency,
                              max_seq=prompt + gen, mesh=mesh)
            reqs = run_workload(
                eng, [(p, gen, dict(GREEDY_KW, seed=11 + i))
                      for i, p in enumerate(prompts)])
            return eng, reqs

        t0 = time.perf_counter()
        eng, reqs = once()  # warm: compiles ride this run
        compile_s = time.perf_counter() - t0
        outs = [(r.result()[0], list(r.log_probs)) for r in reqs]
        best, ticks, ttfts = float("inf"), 0, []
        for _ in range(reps):
            t0 = time.perf_counter()
            eng, reqs = once()
            dt = time.perf_counter() - t0
            if dt < best:
                best, ticks = dt, eng.ticks
                ttfts = [r.ttft for r in reqs if r.ttft is not None]
        total = concurrency * gen
        return eng, outs, {
            "pp": pp, "tp": tp, "chips": max(pp * tp, 1),
            "engine_s": round(best, 4),
            "decode_tok_s": round(total / best, 1),
            "tick_ms": round(best / max(ticks, 1) * 1e3, 3),
            "ticks": ticks,
            "ttft_mean_ms": round(
                1e3 * sum(ttfts) / max(len(ttfts), 1), 2),
            "compile_time_s": round(compile_s, 1),
            "kv_pool_bytes": eng.pool.kv_pool_bytes(),
            "kv_stage_bytes": eng.pool.kv_stage_bytes(),
        }

    # flat identity reference, then every GSPMD (pp=1) arm, THEN the pp
    # arms — a pp engine flips the partitioner for the process lifetime
    _, ref_outs, flat_row = run_arm(1, 1)
    rows, pairs = [flat_row], []
    identity_ok, stage_bytes_ok, hlo = True, True, ""
    for pp in pps:
        _, base_outs, base_row = run_arm(1, pp)  # tp=pp: equal chips
        rows.append(base_row)
        pairs.append((pp, base_row))
        for (t0, l0), (t1, l1) in zip(ref_outs, base_outs):
            identity_ok &= (t0 == t1) and bool(
                np.allclose(l0, l1, atol=5e-6))
    for i, pp in enumerate(pps):
        eng, outs, row = run_arm(pp, 1)
        rows.append(row)
        for (t0, l0), (t1, l1) in zip(ref_outs, outs):
            identity_ok &= (t0 == t1) and bool(
                np.allclose(l0, l1, atol=5e-6))
        stage_bytes_ok &= (row["kv_stage_bytes"]
                           == row["kv_pool_bytes"] // pp)
        pairs[i] = pairs[i] + (row,)
        if not hlo:
            # mechanism, not vibes: the stage-boundary ppermutes run
            # under the stage-permute scope in the compiled tick forward
            from megatron_llm_tpu.generation.engine import PagedState
            from megatron_llm_tpu.models.language_model import (
                make_rope_cache, model_forward,
            )

            bt = np.zeros((eng.max_slots, eng.pages_per_seq), np.int32)
            pos = np.zeros((eng.max_slots,), np.int32)
            toks = np.full((eng.max_slots,), 2, np.int32)
            ppc, acfg = eng._ppc, eng.cfg

            def tickish(p, pool_kv):
                import jax.numpy as jnp

                rope = make_rope_cache(acfg)
                with pp_serve_mod.activate(ppc):
                    logits, _ = model_forward(
                        acfg, p, jnp.asarray(toks)[:, None],
                        position_ids=jnp.asarray(pos)[:, None],
                        rope_cache=rope, kv_caches=pool_kv,
                        paged=PagedState(jnp.asarray(bt),
                                         jnp.asarray(pos)))
                return logits

            hlo = jax.jit(tickish).lower(
                eng.params, eng.pool.kv).compile().as_text()
    mechanism_ok = (pp_serve_mod.STAGE_PERMUTE_SCOPE in hlo
                    and "collective-permute" in hlo)
    ratios = {f"pp{pp}": round(
        pprow["decode_tok_s"] / max(base["decode_tok_s"], 1e-9), 3)
        for pp, base, pprow in pairs}
    headline_pp = max(pps)
    headline = ratios[f"pp{headline_pp}"]
    return {
        "concurrency": concurrency, "prompt_len": prompt, "gen_len": gen,
        "pps": list(pps),
        "decode_tok_s_ratio": headline,
        "ratios_vs_equal_chip_pp1": ratios,
        "identity_ok": identity_ok,
        "stage_bytes_ok": stage_bytes_ok,
        "mechanism_ok": mechanism_ok,
        "stage_bytes_ratio": round(
            rows[-1]["kv_stage_bytes"]
            / max(rows[-1]["kv_pool_bytes"], 1), 4),
        "pp_ok": (identity_ok and stage_bytes_ok and mechanism_ok
                  and min(ratios.values()) >= 0.85),
        "compile_time_s": round(sum(r["compile_time_s"] for r in rows), 1),
        "step_time_s": round(rows[-1]["tick_ms"] / 1e3, 6),
        "rows": rows,
    }


def _run(args, finished):
    layers, hidden, heads, ffn, vocab = 24, 1024, 16, 4096, 32000
    levels = [int(x) for x in args.concurrency.split(",")]
    prefix_mode = args.mode == "shared_prefix"
    slo_mode = args.mode == "slo"
    spec_mode = args.mode == "spec"
    router_mode = args.mode == "router"
    cap_mode = args.mode == "capacity"
    stream_mode = args.mode == "streaming"
    disagg_mode = args.mode == "disagg"
    pp_mode = args.mode == "pp"
    burst = 12  # admission-arm clients (streaming mode section 2)
    draft_layers = 2
    # capacity-mode workload shape (ISSUE 13): ref_slots sizes the fixed
    # byte budget (a bf16 pool for that many concurrent sequences),
    # n_requests over-subscribes it so the peak is pool-bound, and the
    # tenant grid (groups x per_group revisits on shared_len-token
    # prompts) measures the hit-rate dividend at the same bytes
    cap = dict(n_requests=32, ref_slots=8, groups=8, per_group=4,
               shared=256, tail=32, gen_cache=32)
    # disagg-mode workload shape (ISSUE 19): a saturated short-prompt
    # decode class + a long-prompt prefill class, unified fleet vs
    # 1-prefill + 1-decode split at equal chip count
    dg = dict(slots=8, n_short=6, n_long=4, short_reqs=4, long_reqs=2,
              prompt_short=64, gen_short=64, prompt_long=1536, gen_long=32,
              long_chars=512)
    if probe_backend() == "cpu":
        from megatron_llm_tpu.utils.platform import pin_cpu_platform

        # pp mode shards engines over pp x tp virtual chips
        pin_cpu_platform(n_devices=8 if pp_mode else None)
        # CPU sanity shape: small enough for tier-1 time, big enough that
        # the >=3x batching / >=2x prefill-reuse / >=2x slo-TTFT / >=1.3x
        # spec gates are real measurements, not noise
        layers, args.prompt, args.gen, args.reps = 2, 32, 24, 1
        hidden, heads, ffn, vocab = 256, 4, 512, 1024
        args.shared, args.tail = 96, 8
        args.slots, args.n_hi, args.n_lo = 2, 6, 6
        args.gen_lo, args.ttft_slo = 48, 250.0
        if router_mode:
            # prefill-heavy fleet shape: the shared prefix dominates each
            # request (384 prefix tokens vs 8 generated), so WHERE a
            # request lands (cache hot vs cold) is what the TTFT measures;
            # 6 prompt families keep the hash ring's split of groups
            # across 2 replicas near-even
            args.shared, args.tail, args.gen = 384, 8, 8
            args.groups, args.per_group = 6, 6
            args.slots = 4
        if spec_mode:
            # the target must out-depth the 1-layer draft by enough that
            # drafting is visibly cheaper than verifying
            layers, args.gen, draft_layers = 4, 48, 1
        if stream_mode:
            # enough decode ticks (gen=24) that a streamed client's first
            # byte lands visibly before the buffered client's only byte;
            # 4 slots/replica so the c=8 streamed arm saturates a
            # 2-replica fleet without queueing
            args.prompt, args.gen = 48, 24
            args.slots = 4
        if cap_mode:
            # over-subscribe a 3-sequence bf16 budget 4x; 4 tenants whose
            # shared pages (4 x 4 pages) outgrow the bf16 budget but fit
            # the int8 one — both gates are real capacity measurements
            cap = dict(n_requests=12, ref_slots=3, groups=4, per_group=4,
                       shared=64, tail=8, gen_cache=8)
        if disagg_mode:
            # the short class OVER-saturates the fleet (8 clients on 4
            # slots/replica) so the per-tick decode batch is identical in
            # both arms — queueing lands in TTFT, never TPOT — and the
            # TPOT comparison isolates tick COMPOSITION: 512-token
            # prefill chunks sharing the decode ticks (unified) vs pure
            # decode ticks behind the handoff (split)
            dg = dict(slots=4, n_short=8, n_long=3, short_reqs=3,
                      long_reqs=2, prompt_short=24, gen_short=24,
                      prompt_long=512, gen_long=8, long_chars=128)
        if pp_mode:
            # GEMM-dominated shape: with the fill/drain cond-skip the pp
            # arms run the SAME valid GEMM work as the flat tick, so the
            # honest comparison needs per-layer compute large enough
            # that the stage-scan structure (ppermute + psum + cond per
            # scan tick) is small against it — exactly the TPU regime,
            # where the stage-boundary transfer hides behind real GEMM
            # time.  4 layers split evenly over pp in {2, 4}; heads=4 so
            # the tp=4 equal-chip baseline shards the heads dim; long
            # decode streams keep prefill to the first ticks; c=4 rows
            # microbatch M=pp.
            layers, hidden, heads, ffn, vocab = 4, 128, 4, 256, 256
            args.prompt, args.gen, args.reps = 16, 48, 3
            levels = [8]

    import jax

    from megatron_llm_tpu.core.parallel_state import build_mesh, global_mesh
    from megatron_llm_tpu.models import init_model_params, make_config
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()

    seq_need = max(args.prompt + args.gen,
                   args.shared + args.tail + args.gen,
                   args.prompt + args.gen_lo,
                   cap["shared"] + cap["tail"] + cap["gen_cache"],
                   dg["prompt_long"] + max(dg["gen_short"],
                                           dg["gen_long"]) + 1)
    cfg = make_config(
        "llama2", num_layers=layers, hidden_size=hidden,
        num_attention_heads=heads, num_attention_heads_kv=heads,
        ffn_hidden_size=ffn, vocab_size=vocab,
        seq_length=max(2048, seq_need),
        max_position_embeddings=max(2048, seq_need),
        params_dtype="bfloat16" if jax.default_backend() != "cpu"
        else "float32",
        micro_batch_size=1, global_batch_size=1, train_iters=1,
    )
    mesh = build_mesh(devices=jax.devices()[:1])
    with global_mesh(mesh):
        params = init_model_params(cfg, jax.random.PRNGKey(0))
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        if stream_mode:
            row = bench_streaming(cfg, params, args.replicas, levels[-1],
                                  args.prompt, args.gen, vocab, args.slots,
                                  burst)
        elif disagg_mode:
            row = bench_disagg(cfg, params, dg["prompt_short"],
                               dg["gen_short"], dg["prompt_long"],
                               dg["gen_long"], dg["n_short"], dg["n_long"],
                               dg["short_reqs"], dg["long_reqs"], vocab,
                               dg["slots"], dg["long_chars"])
        elif router_mode:
            row = bench_router(cfg, params, args.replicas, args.groups,
                               args.per_group, args.shared, args.tail,
                               args.gen, vocab, args.slots)
        elif cap_mode:
            row = bench_capacity(cfg, params, cap["n_requests"],
                                 cap["ref_slots"], args.prompt, args.gen,
                                 vocab, cap["groups"], cap["per_group"],
                                 cap["shared"], cap["tail"],
                                 cap["gen_cache"])
        elif pp_mode:
            pps = [p for p in (2, 4)
                   if p <= len(jax.devices())
                   and cfg.model.num_layers % p == 0]
            assert pps, "pp mode needs >= 2 devices"
            row = bench_pp(cfg, params, pps, levels[-1], args.prompt,
                           args.gen, vocab, args.reps)
        elif prefix_mode:
            c = levels[-1]
            row = bench_shared_prefix(cfg, params, c, args.shared,
                                      args.tail, args.gen, vocab)
        elif spec_mode:
            from megatron_llm_tpu.generation import DraftModel
            from megatron_llm_tpu.generation.speculative import (
                extend_params_identity,
            )

            dcfg = make_config(
                "llama2", num_layers=draft_layers, hidden_size=hidden,
                num_attention_heads=heads, num_attention_heads_kv=heads,
                ffn_hidden_size=ffn, vocab_size=vocab,
                seq_length=max(2048, seq_need),
                max_position_embeddings=max(2048, seq_need),
                params_dtype=cfg.training.params_dtype,
                use_flash_attn=cfg.training.use_flash_attn,
                micro_batch_size=1, global_batch_size=1, train_iters=1,
            )
            dparams = init_model_params(dcfg, jax.random.PRNGKey(1))
            params = extend_params_identity(dcfg, dparams, cfg,
                                            jax.random.PRNGKey(0))
            row = bench_spec(cfg, params, DraftModel(dcfg, dparams),
                             levels, args.prompt, args.gen, vocab,
                             args.spec_k, args.reps)
        elif slo_mode:
            row = bench_slo(cfg, params, args.slots, args.n_hi, args.n_lo,
                            args.prompt, args.gen, args.gen_lo, vocab,
                            args.ttft_slo)
        else:
            rows = [bench_engine(cfg, params, c, args.prompt, args.gen,
                                 vocab, args.reps) for c in levels]

    if stream_mode:
        result = {
            "metric": METRIC_STREAMING,
            "value": row["first_token_speedup"],
            "unit": "x",
            "first_token_speedup": row["first_token_speedup"],
            "stream_ok": row["stream_ok"],
            "stamp_ratio": row["stamp_ratio"],
            "stamp_ok": row["stamp_ok"],
            "buffered_first_byte_is_total":
                row["buffered_first_byte_is_total"],
            "identity_ok": row["identity_ok"],
            "baseline_dropped": row["baseline_dropped"],
            "admission_dropped": row["admission_dropped"],
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("n_replicas", "concurrency", "prompt_len",
                          "gen_len", "slots", "burst")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_streaming"
    elif disagg_mode:
        result = {
            "metric": METRIC_DISAGG,
            "value": row["decode_tpot_p99_speedup"],
            "unit": "x",
            "decode_tpot_p99_speedup": row["decode_tpot_p99_speedup"],
            "decode_tpot_mean_speedup": row["decode_tpot_mean_speedup"],
            "disagg_ok": row["disagg_ok"],
            "identity_ok": row["identity_ok"],
            "handoffs": row["handoffs"],
            "handoff_failures": row["handoff_failures"],
            "long_ttft_mean_ms": row["long_ttft_mean_ms"],
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("n_replicas", "slots", "prompt_short",
                          "gen_short", "prompt_long", "gen_long",
                          "n_short", "n_long", "short_reqs", "long_reqs",
                          "long_prompt_chars")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_disagg"
    elif router_mode:
        result = {
            "metric": METRIC_ROUTER,
            "value": row["ttft_mean_speedup"],
            "unit": "x",
            "speedup_ok": row["speedup_ok"],
            "fleet_hit_rate_gain": row["fleet_hit_rate_gain"],
            "failover": row["failover"],
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("n_replicas", "groups", "per_group", "shared_len",
                          "tail_len", "gen_len")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_router"
    elif cap_mode:
        result = {
            "metric": METRIC_CAPACITY,
            "value": row["slot_ratio"],
            "unit": "x",
            "capacity_ok": row["capacity_ok"],
            "greedy_match": row["greedy_match"],
            "slot_ratio": row["slot_ratio"],
            "page_ratio": row["page_ratio"],
            "pool_budget_bytes": row["pool_budget_bytes"],
            "hit_rate_bf16": row["hit_rate_bf16"],
            "hit_rate_int8": row["hit_rate_int8"],
            "hit_rate_gain": row["hit_rate_gain"],
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("n_requests", "ref_slots", "prompt_len",
                          "gen_len", "groups", "per_group", "shared_len")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_capacity"
    elif spec_mode:
        result = {
            "metric": METRIC_SPEC,
            "value": row["speedup_c1"],
            "unit": "x",
            "speedup_ok": row["speedup_ok"],
            "acceptance_rate": row["acceptance_rate"],
            "spec_k": row["spec_k"],
            "draft_layers": draft_layers,
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in ("prompt_len", "gen_len")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_spec"
    elif slo_mode:
        by = {r["policy"]: r for r in row["rows"]}
        result = {
            "metric": METRIC_SLO,
            "value": row["hi_p99_ttft_speedup"],
            "unit": "x",
            "speedup_ok": row["speedup_ok"],
            "hi_deadline_miss_rate": {
                p: by[p]["hi"]["deadline_miss_rate"] for p in by},
            "preemptions": {p: by[p]["preemptions"] for p in by},
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("slots", "n_hi", "n_lo", "prompt_len", "gen_hi",
                          "gen_lo", "ttft_slo_ms")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_slo"
    elif pp_mode:
        result = {
            "metric": METRIC_PP.replace(
                "_c4_", f"_c{row['concurrency']}_"),
            "value": row["decode_tok_s_ratio"],
            "unit": "x",
            "pp_ok": row["pp_ok"],
            "identity_ok": row["identity_ok"],
            "stage_bytes_ok": row["stage_bytes_ok"],
            "mechanism_ok": row["mechanism_ok"],
            "stage_bytes_ratio": row["stage_bytes_ratio"],
            "ratios_vs_equal_chip_pp1": row["ratios_vs_equal_chip_pp1"],
            "compile_time_s": row["compile_time_s"],
            "step_time_s": row["step_time_s"],
            "n_params": n_params,
            "rows": row["rows"],
            "workload": {k: row[k] for k in
                         ("concurrency", "prompt_len", "gen_len", "pps")},
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_pp"
    elif prefix_mode:
        result = {
            "metric": METRIC_PREFIX.replace(
                "_c8_", f"_c{row['concurrency']}_"),
            "value": row["prefill_token_reduction"],
            "unit": "x",
            "ttft_mean_speedup": row["ttft_mean_speedup"],
            "hit_rate": row["cache_on"]["hit_rate"],
            "n_params": n_params,
            "rows": [row],
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode_prefix"
    else:
        headline = rows[-1]
        result = {
            "metric": METRIC.replace(
                "_c8_", f"_c{headline['concurrency']}_"),
            "value": headline["engine_tok_s"],
            "unit": "tok/s",
            "speedup_vs_sequential": headline["speedup_vs_sequential"],
            "n_params": n_params,
            "rows": rows,
            "backend": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        tag = "engine_decode"
    if result["backend"] == "cpu":
        result = cpu_contract_line(result, tag=tag)
    finished.set()
    print(json.dumps(result), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode",
                    choices=("occupancy", "shared_prefix", "slo", "spec",
                             "router", "capacity", "streaming", "disagg",
                             "pp"),
                    default="occupancy")
    ap.add_argument("--concurrency", default="1,4,8",
                    help="comma-separated occupancy levels (requests); "
                         "shared_prefix uses the last level, spec sweeps "
                         "all of them (headline at c=1)")
    ap.add_argument("--spec_k", type=int, default=4,
                    help="speculation depth cap (spec mode)")
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--gen", type=int, default=128)
    ap.add_argument("--shared", type=int, default=256,
                    help="shared system-prompt tokens (shared_prefix mode)")
    ap.add_argument("--tail", type=int, default=32,
                    help="distinct per-request prompt tail (shared_prefix)")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots (slo mode; overload = requests >> slots)")
    ap.add_argument("--n_hi", type=int, default=16,
                    help="interactive priority-0 requests (slo mode)")
    ap.add_argument("--n_lo", type=int, default=16,
                    help="batch priority-2 requests (slo mode)")
    ap.add_argument("--gen_lo", type=int, default=256,
                    help="batch-request generation length (slo mode)")
    ap.add_argument("--ttft_slo", type=float, default=2000.0,
                    help="interactive TTFT deadline in ms (slo mode)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet size (router mode)")
    ap.add_argument("--groups", type=int, default=4,
                    help="shared-prefix prompt families (router mode)")
    ap.add_argument("--per_group", type=int, default=6,
                    help="requests per prompt family incl. the warm one "
                         "(router mode)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--watchdog", type=float, default=1500.0)
    args = ap.parse_args()

    if args.mode == "spec" and args.concurrency == "1,4,8":
        args.concurrency = "1,2,4,8"
    metric = {"shared_prefix": METRIC_PREFIX, "slo": METRIC_SLO,
              "spec": METRIC_SPEC, "router": METRIC_ROUTER,
              "capacity": METRIC_CAPACITY,
              "streaming": METRIC_STREAMING,
              "disagg": METRIC_DISAGG,
              "pp": METRIC_PP}.get(args.mode, METRIC)
    unit = ("x" if args.mode in ("shared_prefix", "slo", "spec", "router",
                                 "capacity", "streaming", "disagg", "pp")
            else "tok/s")
    finished = threading.Event()

    def on_timeout():
        if finished.is_set():
            return
        print(json.dumps({
            "metric": metric, "value": 0.0, "unit": unit,
            "error": f"watchdog: engine decode bench exceeded "
                     f"{args.watchdog}s",
        }), flush=True)
        os._exit(3)

    dog = threading.Timer(args.watchdog, on_timeout)
    dog.daemon = True
    dog.start()

    try:
        _run(args, finished)
    except Exception as e:  # structured error line, never a bare traceback
        finished.set()
        print(json.dumps({
            "metric": metric, "value": 0.0, "unit": unit,
            "error": f"{type(e).__name__}: {e}",
        }), flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
