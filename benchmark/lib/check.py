"""The comparison that decides ``correct``: the program against the plain
float32 reference on the same weights, at the run's own (published) widths,
outside the measured window.  Log-probabilities are compared, never tokens:
with random weights the largest logit changes on rounding.

Training compares the program's per-token loss on seeded sequences.
Serving compares what the measured path itself emits: seeded probe prompts
are streamed through the HTTP API with greedy sampling, and the
log-probability the engine reports for each token it emitted (the last
prefill chunk's row, then decode rows of the ragged tick, all through the
paged kernel and the KV pool) is held against the reference's
log-probability of that same token given the prompt and the tokens emitted
before it.  Two of the probes start behind another's prompt, so that their
keys come out of the prefix cache: one by a whole page-aligned match (the
copy-on-write page) and one by a partial match with its own suffix.

Tolerance.  The configurations state bf16 weights and activations with
float32 accumulation; the reference is float32 at ``highest`` precision, so
the two differ by bf16 rounding noise that grows with depth and width.
Each configuration file carries its own limits under ``tolerance``
(``mean_abs_nats`` over the compared tokens, ``max_abs_nats`` for the worst
one), set at three to four times what that cell read on the chip, with the
readings beside them (benchmark/README.md has the table).  What such a
limit is known to catch is what a run has shown: a single token 0.96 nats
off in the four-chip training layout (PERF.md 7a).  That a lower-precision
forward (fp8 products, bf16 accumulation) fails it is expected from the
arithmetic and has not been shown by a run.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

PROBE_TOKENS = 32       # greedy tokens streamed per serving probe


def reference_module(cell):
    return importlib.import_module(
        "benchmark.reference." + cell.config["reference"])


def compare(cell, program_lp, reference_lp) -> Dict:
    import numpy as np

    tol = cell.config["tolerance"]
    a = np.asarray(program_lp, np.float64).ravel()
    b = np.asarray(reference_lp, np.float64).ravel()
    if a.shape != b.shape or a.size == 0:
        return {"reference_ok": False, "reference_why":
                f"shapes differ: program {a.shape}, reference {b.shape}"}
    diff = np.abs(a - b)
    finite = bool(np.isfinite(a).all() and np.isfinite(b).all())
    ok = (finite and diff.mean() <= float(tol["mean_abs_nats"])
          and diff.max() <= float(tol["max_abs_nats"]))
    return {"reference_ok": bool(ok), "reference_tokens": int(a.size),
            "reference_mean_abs_diff": float(diff.mean()) if finite else None,
            "reference_max_abs_diff": float(diff.max()) if finite else None,
            "reference_mean_logprob": float(b.mean()) if finite else None}


def reference_log_probs(cell, params, tokens):
    """log p(token[i+1] | tokens[:i+1]) for ``tokens`` [rows, n] from the
    plain reference on ``params``."""
    import jax.numpy as jnp

    from benchmark.reference import common

    ref = reference_module(cell)
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = ref.logits(params, tokens, cell.model)
    return common.token_log_probs(logits, tokens)


def train_against_reference(cell, cfg, params, mesh, seed: int, rows: int,
                            positions: int) -> Dict:
    """The program's per-token loss on ``rows`` seeded sequences of
    ``positions`` tokens against the reference on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import traffic as traffic_mod
    from megatron_llm_tpu.core.parallel_state import global_mesh
    from megatron_llm_tpu.models.language_model import model_forward
    from megatron_llm_tpu.training_step import batch_shardings

    toks = np.asarray(traffic_mod.probe_tokens(
        seed, rows, positions + 1, cfg.model.vocab_size), np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with global_mesh(mesh):
        placed = jax.device_put(batch, batch_shardings(cfg, mesh, batch))
        loss = jax.jit(lambda p, b: model_forward(
            cfg, p, b["tokens"], labels=b["labels"])[0])(params, placed)
        program_lp = -np.asarray(jax.device_get(loss))
        ref_lp = np.asarray(jax.device_get(
            reference_log_probs(cell, params, jnp.asarray(toks))))
    return compare(cell, program_lp, ref_lp)


def seeded_prompts(seed: int, vocab: int, lengths) -> List[List[int]]:
    from benchmark.lib import traffic as traffic_mod

    return [traffic_mod.probe_tokens(seed + i, 1, n, vocab)[0]
            for i, n in enumerate(lengths)]


def serve_probes(seed: int, vocab: int, lengths, page: int) -> List[Dict]:
    """The serving probes, in the order they are sent.  ``alone`` and
    ``first`` are streamed together; ``whole_hit`` is the pages ``first``
    left in the prefix cache and nothing else (a page-aligned whole match:
    the engine copies the last page before it writes to it); ``part_hit``
    leaves ``first`` half a page earlier and goes on with tokens of its
    own."""
    alone, first, fresh = seeded_prompts(seed, vocab, (*lengths, lengths[1]))
    cached = (len(first) - 1) // page * page
    cut = cached - page // 2
    return [{"name": "alone", "prompt": alone, "after": None},
            {"name": "first", "prompt": first, "after": None},
            {"name": "whole_hit", "prompt": first[:cached], "after": "first"},
            {"name": "part_hit", "prompt": first[:cut] + fresh[cut:],
             "after": "first"}]


def serve_against_reference(cell, params, probes: List[Dict]) -> Dict:
    """Each probe holds its ``prompt`` and the ``tokens`` / ``logprobs`` its
    stream carried.  The reference runs once on all of them, right-padded
    to one length (causal: what follows a position does not reach it)."""
    import jax
    import numpy as np

    seqs = [p["prompt"] + p["tokens"] for p in probes]
    width = max(len(s) for s in seqs)
    batch = np.ones((len(seqs), width), np.int32)
    for row, s in zip(batch, seqs):
        row[:len(s)] = s
    ref = np.asarray(jax.device_get(reference_log_probs(cell, params, batch)))
    got, want = [], []
    for p, row in zip(probes, ref):
        start = len(p["prompt"]) - 1      # predicts the first emitted token
        got += p["logprobs"]
        want += row[start:start + len(p["tokens"])].tolist()
    out = compare(cell, got, want)
    out["probe_tokens"] = {p["name"]: len(p["tokens"]) for p in probes}
    return out
