"""The comparison that decides ``correct``: the program against the plain
float32 reference on the same weights, at the run's own (published) widths,
outside the measured window.  Log-probabilities are compared, never tokens:
with random weights the largest logit changes on rounding.

Training compares the program's per-token loss on seeded sequences, on the
weights the trainer returns: the state after the window's steps, so a
configuration's learning rate is part of what the comparison reads
(SmallThinker trains at 1e-6, where that state reads as the initialiser's
for every seed and step count: PERF.md section 6, PR 38).
Serving compares what the measured path itself emits: seeded probe prompts
are streamed through the HTTP API with greedy sampling, and the
log-probability the engine reports for each token it emitted (the last
prefill chunk's row, then decode rows of the ragged tick, all through the
paged kernel and the KV pool) is held against the reference's
log-probability of that same token given the prompt and the tokens emitted
before it.  Two of the probes start behind another's prompt, so that their
keys come out of the prefix cache: one by a whole page-aligned match (the
copy-on-write page) and one by a partial match with its own suffix.

Memory of the serving comparison.  The reference runs one probe at a time
and its head only on the rows whose tokens were emitted, so a probe of
``n`` tokens (prompt + emitted) of a model with ``heads`` query heads,
hidden width ``h`` and ``vocab`` rows costs, in float32 temporaries: the
attention scores of one block of queries, ``4 * heads * block * n`` bytes
with ``block = reference/common.query_block(n, heads)`` (n itself up to 512
positions, then at most 512 and at most what keeps the scores under
256 MiB), a few times over for the mask and the softmax; the residual
stream and a layer's projections, some multiples of ``4 * n * h``; one
layer's weights cast to float32; and at the head ``4 * vocab * h`` for the
cast matrix beside ``4 * PROBE_TOKENS * vocab`` of logits, where the whole
logits would be ``4 * n * vocab``.  At 4,640 tokens, 32 heads and 129,280
rows: scores 0.24 GB a block, the head's matrix 1.06 GB, logits 17 MB
(whole: 2.4 GB a row).  An expert layer adds what its reference says.

Tolerance.  The configurations state bf16 weights and activations with
float32 accumulation; the reference is float32 at ``highest`` precision, so
the two differ by bf16 rounding noise that grows with depth and width.
Each configuration file carries its own limits under ``tolerance``
(``mean_abs_nats`` over the compared tokens, ``max_abs_nats`` for the worst
one, and ``median_abs_nats`` where a configuration gives one: a discrete
router moves a few tokens by tenths of a nat, which sets the mean of 128
and leaves their median alone), each between what the program reads over a
dozen seeds and what a lower precision or a planted fault reads, with the
readings beside them (benchmark/README.md has the table).  What the limits
are known to catch is what runs have shown: a single token 0.96 nats off
in the four-chip training layout (PERF.md 7a); the reference in bfloat16
throughout (``benchmark/control.py``: JoyAI by its median; SmallThinker by
its median and its mean); a window ignored, RoPE misplaced, a router fed the wrong norm
or forgetting its selection bias; keys older than 4,096 dropped at probes
of 4,352 and 4,608 tokens (PERF.md section 6, PR 38).
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
    read = {"mean": diff.mean(), "median": np.median(diff), "max": diff.max()}
    # a configuration compares the statistics it gives a limit for
    ok = finite and all(read[name] <= float(tol[name + "_abs_nats"])
                        for name in read if name + "_abs_nats" in tol)
    out = {"reference_ok": bool(ok), "reference_tokens": int(a.size)}
    for name, value in read.items():
        out[f"reference_{name}_abs_diff"] = float(value) if finite else None
    out["reference_mean_logprob"] = float(b.mean()) if finite else None
    return out


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


def emitted_reference(cell, params, probes: List[Dict]) -> List[float]:
    """The reference's log-probability of every token the probes' streams
    carried (each probe holds its ``prompt`` and ``tokens``).  Its stack runs
    on one probe at a time, each right-padded to the longest (causal: what
    follows a position does not reach it; one width, so one compiled program
    a layer), and its head on the rows that predicted the emitted tokens
    only: rows ``[len(prompt) - 1, len(prompt) - 1 + len(tokens))``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import common

    ref = reference_module(cell)
    width = max(len(p["prompt"]) + len(p["tokens"]) for p in probes)
    want: List[float] = []
    for p in probes:
        seq = p["prompt"] + p["tokens"]
        row = np.ones((1, width), np.int32)
        row[0, :len(seq)] = seq
        hidden = ref.stack(params, jnp.asarray(row), cell.model)[0]
        start = len(p["prompt"]) - 1      # predicts the first emitted token
        logits = ref.head(params, hidden[start:start + len(p["tokens"])],
                          cell.model)
        lp = common.emitted_log_probs(logits, jnp.asarray(p["tokens"], jnp.int32))
        want += np.asarray(jax.device_get(lp), np.float32).tolist()
    return want


def serve_against_reference(cell, params, probes: List[Dict]) -> Dict:
    """The log-probabilities the probes' streams carried against
    ``emitted_reference``."""
    got = [lp for p in probes for lp in p["logprobs"]]
    out = compare(cell, got, emitted_reference(cell, params, probes))
    out["probe_tokens"] = {p["name"]: len(p["tokens"]) for p in probes}
    return out
