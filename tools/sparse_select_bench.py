"""Time the pieces of learned sparse attention (ops/sparse_attention.py) on
the chip at one cell's shapes, the exact selection as a threshold beside ``lax.top_k``
(checked to pick the same sets), and a tile's two paths (rows of tables of
their own against rows of ONE table) side by side.

    chiprun -- python tools/sparse_select_bench.py [--rows 128 256] \
        [--context 33400] [--width 2176] [--topk 2048]

Random index keys and latent rows in a pool of ``--pages`` pages, every row
a table of its own over random pages (no two rows share a page: what the
sweep and the gather cost when nothing is shared).  Prints a JSON line a
piece and row count: milliseconds a call (median of ``--reps`` after a
warm-up), and what the piece needs at the chip's HBM peak.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[32, 128])
    ap.add_argument("--context", type=int, default=33400)
    ap.add_argument("--width", type=int, default=2176, help="pages a table")
    ap.add_argument("--pages", type=int, default=20481)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--attn_heads", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=640)
    ap.add_argument("--value_width", type=int, default=512)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_llm_tpu.ops import sparse_attention as sa

    page = 16
    key = jax.random.PRNGKey(0)
    index_leaf = jax.random.normal(
        key, (1, args.pages, page, args.dim), jnp.bfloat16)
    latent_leaf = jax.random.normal(
        jax.random.fold_in(key, 1), (1, args.pages, page, args.lanes),
        jnp.bfloat16)
    rng = np.random.default_rng(0)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        ts = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ts.append(time.perf_counter() - t)
        return out, 1e3 * sorted(ts)[len(ts) // 2]

    topk, vw = args.topk, args.value_width
    score = jax.jit(lambda q, w, leaf, t, c: sa.index_scores(
        q, w, leaf, 0, t, c))
    select = jax.jit(lambda s, c: sa.select_mask(s, c, topk))
    compact = jax.jit(lambda m: sa.compact(m, topk))
    top_k = jax.jit(lambda s: jax.lax.top_k(s, topk))
    attend = jax.jit(lambda q, leaf, t, i, v: sa.attend_list(
        q, leaf, 0, t, i, v, 0.08, vw))
    masked = jax.jit(lambda q, leaf, t, m, c: sa.attend_masked(
        q, leaf, 0, t, m, c, 0.08, vw))
    whole = jax.jit(lambda qi, w, q, il, ll, t, ix, c: sa.sparse_attention(
        qi, w, q, il, ll, 0, t, ix, c, topk, 0.08, vw))

    def say(piece, rows, ms, **more):
        print(json.dumps({"piece": piece, "rows": rows, "ms": ms, **more}),
              flush=True)

    for rows in args.rows:
        tables = jnp.asarray(rng.integers(
            1, args.pages, (rows, args.width)), jnp.int32)
        ctx = jnp.asarray(rng.integers(
            args.context - 256, args.context, (rows,)), jnp.int32)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (rows, args.heads, args.dim), jnp.bfloat16)
        w = jax.random.normal(jax.random.fold_in(key, 3), (rows, args.heads))
        q_abs = jax.random.normal(
            jax.random.fold_in(key, 4), (rows, args.attn_heads, args.lanes),
            jnp.bfloat16)
        scores, ms = timed(score, q, w, index_leaf, tables, ctx)
        say("index_scores", rows, ms, least_ms_at_hbm_peak=(
            rows * args.context * args.dim * 2 / 819e9 * 1e3))
        sel, ms = timed(select, scores, ctx)
        say("select_mask (threshold)", rows, ms)
        (idx, valid), ms = timed(compact, sel)
        say("compact", rows, ms)
        (_, top), ms = timed(top_k, scores)
        say("lax.top_k", rows, ms, threshold_picks_the_same_sets=all(
            set(np.asarray(a)[np.asarray(v)].tolist())
            == set(np.asarray(b).tolist())
            for a, v, b in zip(idx, valid, top)))
        _, ms = timed(attend, q_abs, latent_leaf, tables, idx, valid)
        say("attend_list", rows, ms, least_ms_at_hbm_peak=(
            rows * topk * 576 * 2 / 819e9 * 1e3))
        # the same rows as ONE table's (a prompt chunk): the shared path
        one = tables[:1]
        _, ms = timed(score, q, w, index_leaf, one, ctx)
        say("index_scores, one table", rows, ms)
        _, ms = timed(masked, q_abs, latent_leaf, one, sel, ctx)
        say("attend_masked, one table", rows, ms)
        _, ms = timed(whole, q, w, q_abs, index_leaf, latent_leaf, tables,
                      jnp.arange(rows, dtype=jnp.int32), ctx)
        say("sparse_attention, lone tiles", rows, ms)
        _, ms = timed(whole, q, w, q_abs, index_leaf, latent_leaf, one,
                      jnp.zeros((rows,), jnp.int32), ctx)
        say("sparse_attention, shared tiles", rows, ms)
    return 0


if __name__ == "__main__":
    sys.exit(main())
