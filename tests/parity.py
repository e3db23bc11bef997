"""Parity assertions shared by the engine suites.

PRs 5, 9 and 11 stated their contracts bit for bit ("CPU reductions are
bitwise-stable at 64-token horizons"): a property of XLA:CPU as the jax of
that time shipped it.  Under jax 0.9.0 two PROGRAMS that compute the same math — the
ragged tick and the legacy split dispatch, a chunked prefill and a
monolithic one, a resumed request and its uninterrupted twin — may order a
reduction differently (fusion and vectorization are chosen per program
shape), and their fp32 log-probs then differ in the last bits: the one
inspected was -4.6233253 vs -4.6233258, one ulp.

So the contract is what the engine owes its users: the SAME TOKENS, and
log-probs equal to within a few ulps of an fp32 log-prob.  |log p| stays
under 16 on these vocabularies (ulp 9.5e-7 to 1.9e-6), so 5e-6 is a few
ulps — the bound the tp>1 and overlap suites have always used for the same
reason.  A real divergence (a wrong page, a stale KV row, a skipped mask)
moves a log-prob by 1e-3 or more and flips tokens.
"""

from __future__ import annotations

import numpy as np

LOGPROB_ATOL = 5e-6


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
