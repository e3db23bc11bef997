"""Parity assertions shared by the engine suites.

PRs 5, 9 and 11 stated their contracts bit for bit ("CPU reductions are
bitwise-stable at 64-token horizons"): a property of XLA:CPU as the jax of
that time shipped it.  Under jax 0.9.0 two PROGRAMS that compute the same math — a
tick that carries prompt rows and one that does not, two chunk sizes, a
resumed request and its uninterrupted twin — may order a
reduction differently (fusion and vectorization are chosen per program
shape), and their fp32 log-probs then differ in the last bits: the one
inspected was -4.6233253 vs -4.6233258, one ulp.

So the contract is what the engine owes its users: the SAME TOKENS, and
log-probs equal to within a few ulps of an fp32 log-prob.  |log p| stays
under 16 on these vocabularies (ulp 9.5e-7 to 1.9e-6), so 5e-6 is a few
ulps — the bound the tp>1 and overlap suites have always used for the same
reason.  A real divergence (a wrong page, a stale KV row, a skipped mask)
moves a log-prob by 1e-3 or more and flips tokens.

What the engine is compared WITH is never a second copy of itself under
another option (such a twin shares the pool, the allocator, the trie, the
planner and the sampler, and a fault in shared code passes both sides):

* the **anchor** (:func:`assert_greedy_match_dense`): for a greedy job the
  dense single-stream path, which knows nothing of pages, slots or ticks —
  tokens equal to ``generate_tokens``, generation and prompt log-probs
  equal to ``score_tokens`` within ``DENSE_ATOL`` (two different programs
  in fp32: the 2e-4 of tests/test_paged_engine.py);
* the **tight one** (:func:`serve_alone`): for every job, sampled ones
  included (their keys are the engine's own per-request stream, which the
  dense sampler does not draw), the same engine serving that request
  alone, under :func:`assert_same_generations`.  What a mixed tick can get
  wrong that a lone request cannot is another request's page, row or mask.

Jobs are ``(prompt, n_new, submit_kwargs)``; greedy ones carry
``top_k=1`` and a ``termination_id`` no token reaches.
"""

from __future__ import annotations

import jax
import numpy as np

LOGPROB_ATOL = 5e-6
DENSE_ATOL = 2e-4


def assert_logprobs_close(a, b, what: str = "log-probs") -> None:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape, f"{what}: {a.shape} vs {b.shape}"
    np.testing.assert_allclose(a, b, rtol=0, atol=LOGPROB_ATOL,
                               err_msg=what)


def assert_same_generations(a, b, what: str = "streams") -> None:
    """``a``/``b``: per-request ``(tokens, log_probs)`` results."""
    assert len(a) == len(b), what
    for i, ((t0, l0), (t1, l1)) in enumerate(zip(a, b)):
        assert t0 == t1, f"{what}: tokens of request {i} diverged"
        assert_logprobs_close(l0, l1, f"{what}: log-probs of request {i}")


def run_jobs(eng, jobs):
    """Submit all of ``jobs``, drive the engine idle; the finished
    requests."""
    reqs = [eng.submit(p, n, **kw) for p, n, kw in jobs]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=120)
    return reqs


def generations(reqs):
    return [r.result(timeout=120) for r in reqs]


def serve_alone(make_engine, jobs):
    """Each job on a fresh engine of the same making, with nothing else in
    it; the finished requests."""
    return [run_jobs(make_engine(), [job])[0] for job in jobs]


def dense_greedy(cfg, params, prompt, n_new):
    """The dense single-stream path on one greedy job: ``(tokens,
    log_probs)`` with tokens = prompt + ``n_new`` greedy tokens of
    ``generate_tokens`` and ``log_probs[i]`` = ``score_tokens``' log-prob
    of ``tokens[i + 1]``.  Sequences are padded to the BUCKET grid (causal
    attention: the pad changes nothing before it), so a suite compiles a
    handful of dense programs."""
    from megatron_llm_tpu.generation.generation import (
        BUCKET,
        generate_tokens,
        score_tokens,
    )

    total = len(prompt) + n_new
    S = -(-(total + 1) // BUCKET) * BUCKET
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :len(prompt)] = prompt
    res = generate_tokens(
        cfg, params, tokens, np.array([len(prompt)], np.int32), total,
        prefill_len=1, termination_id=10 ** 9,
        sample_key=jax.random.PRNGKey(0), top_k=1)
    full = np.zeros((1, S), np.int32)
    full[0, :total] = np.asarray(res.tokens)[0, :total]
    lp = np.asarray(score_tokens(cfg, params, full))[0, :total - 1]
    return [int(t) for t in full[0, :total]], lp


def assert_greedy_match_dense(cfg, params, jobs, reqs) -> int:
    """The anchor: every greedy job of ``jobs`` (finished as ``reqs``)
    against :func:`dense_greedy`; prompt scores too where the request
    asked for them.  Returns how many jobs it compared."""
    n = 0
    for i, ((prompt, n_new, kw), req) in enumerate(zip(jobs, reqs)):
        if kw.get("top_k") != 1:
            continue
        n += 1
        toks, lps = req.result(timeout=120)
        ref_toks, ref_lp = dense_greedy(cfg, params, list(prompt), n_new)
        assert list(toks) == ref_toks, (
            f"request {i}: tokens diverged from the dense greedy stream")
        first = len(prompt) - 1
        np.testing.assert_allclose(
            np.asarray(lps, np.float64), ref_lp[first:], rtol=0,
            atol=DENSE_ATOL, err_msg=f"request {i}: generation log-probs")
        if req.prompt_log_probs is not None:
            np.testing.assert_allclose(
                np.asarray(req.prompt_log_probs, np.float64),
                ref_lp[:first], rtol=0, atol=DENSE_ATOL,
                err_msg=f"request {i}: prompt log-probs")
    return n


# ---- the engine's per-sequence memory, a class ------------------------------

def held_pages(req) -> list:
    """Every page or slot ``req`` holds, all classes together."""
    return [p for m in req._mem for p in m.pages if p]


def assert_memory(eng) -> None:
    """Between two steps, for every class of per-sequence memory
    (``zip(eng._classes, req._mem)``): a page is referenced once a holder,
    free, cached-idle and referenced pages are disjoint and make up the
    pool, the kept idle count is the walk's; a record's nulls lie behind
    ``first``, ``private`` counts its own live pages and stays under
    ``max``; the ledger is the sum of ``max - private`` and the pool can
    serve it; an installed row of the class's table mirrors its record."""
    from collections import Counter

    for r in list(eng._queue):           # a queued request holds nothing
        assert not held_pages(r)
    live = [r for r in eng._slots if r is not None]
    for k, cls in enumerate(eng._classes):
        pool, mems = cls.pool, [r._mem[k] for r in live]
        holders = Counter(p for m in mems for p in m.pages if p)
        free = set(pool._free)
        assert len(free) == pool.num_free and 0 not in free
        for p in range(1, pool.num_pages):
            assert pool.refcounts[p] == holders.get(p, 0), (cls.name, p)
        referenced = set(holders)
        idle = {p for p in pool.cached if pool.refcounts[p] == 0}
        assert not free & referenced and not free & pool.cached
        assert len(free) + len(referenced) + len(idle) == pool.num_pages - 1
        assert pool.num_evictable == len(idle)     # the kept count is the walk
        own = 0
        for m in mems:
            assert not any(m.pages[:m.first])
            assert m.private == sum(p != 0 for p in m.pages[m.keep:])
            assert m.private <= m.max <= cls.cap
            assert len(m.pages) <= cls.width
            own += m.max - m.private
            if m.row >= 0:
                assert eng._slots[m.row]._mem[k] is m
                assert list(cls.table[m.row, :len(m.pages)]) == m.pages
                assert not cls.table[m.row, len(m.pages):].any()
        assert cls.committed == own          # the ledger is what it says
        assert pool.num_available >= cls.committed
        rows = {m.row for m in mems if m.row >= 0}
        for slot in range(eng.max_slots):
            assert slot in rows or not cls.table[slot].any()


def assert_memory_idle(eng) -> None:
    """Nothing in flight: every ledger at zero, every table null, every
    page free or cached-idle."""
    for cls in eng._classes:
        assert cls.committed == 0 and not cls.table.any()
        assert (cls.pool.num_free + cls.pool.num_evictable
                == cls.pool.num_pages - 1)
        assert not cls.pool.refcounts.any()
