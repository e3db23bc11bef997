"""The one general traffic generator.  A traffic mix is a data file under
``benchmark/traffic/``; this module turns (file, seed, seconds) into the
work of one run.  jax-free and numpy-free at import: the client child
imports it too.

Steadiness rule: the sizes and the inter-arrival gaps are drawn from the
mix's own ``draw_seed``; ``--seed`` picks the token ids (and, in run.py, the
weights) and reorders sizes and gaps inside consecutive blocks of
``SHUFFLE_BLOCK``.  Every seed therefore has a schedule of its own, and any
stretch of it holds the same work as under any other seed (up to one block
at each end), so a difference between two seeds is the system's, not the
dice's.  The blocks matter for a closed loop, whose plan is longer than any
run consumes: a shuffle of the whole plan would hand each seed another
subset of sizes.
"""

from __future__ import annotations

import json
import math
import os
import random
from typing import Dict, Iterator, List

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")
SEED_MASK = (1 << 63) - 1
SHUFFLE_BLOCK = 8


def load(name_or_path: str) -> Dict:
    path = name_or_path
    if not os.path.isfile(path):
        path = os.path.join(TRAFFIC_DIR, name_or_path + ".json")
    with open(path) as f:
        mix = json.load(f)
    if "kind" not in mix:
        raise ValueError(f"{path}: a traffic mix names its kind")
    return mix


def _draw_len(rng: random.Random, spec: Dict) -> int:
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return int(min(max(round(x), spec.get("min", 1)), spec.get("max", 1 << 30)))


def _shuffle_blocks(rng: random.Random, xs: List) -> None:
    """Reorder ``xs`` in place inside consecutive blocks of SHUFFLE_BLOCK."""
    for i in range(0, len(xs), SHUFFLE_BLOCK):
        block = xs[i:i + SHUFFLE_BLOCK]
        rng.shuffle(block)
        xs[i:i + SHUFFLE_BLOCK] = block


def _tokens(rng: random.Random, n: int, vocab: int) -> List[int]:
    # 0 is NullTokenizer's end-of-document id: never in a prompt
    return [rng.randrange(1, vocab) for _ in range(n)]


def request_plan(mix: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    """Every request of one serving run: ``{"requests": [...], "prefixes":
    [[ids]...]}``.  A request is ``{"id", "due_s" (open loop: seconds
    after the ramp starts; closed loop: None), "prompt": [ids], "n_out",
    "prefix" (index or None)}``.  Open loop covers ramp + window; closed
    loop is a list long enough that clients never run out.

    Two limits: ``prompt_len.max`` clamps the BODY a request draws, and
    ``prompt_max`` cuts the whole prompt, shared prefix and body together
    (a mix sets it to what its engine's ``engine_max_seq`` leaves beside
    the longest output).  A mix without ``prompt_max`` cuts the whole
    prompt at ``prompt_len.max`` too, as every mix did before the key."""
    base = random.Random(int(mix.get("draw_seed", 0)))
    order = random.Random(int(seed) & SEED_MASK)
    horizon = float(mix.get("ramp_s", 0.0)) + float(seconds)
    shared = mix.get("shared_prefix") or None

    def size():
        n_prompt = _draw_len(base, mix["prompt_len"])
        n_out = _draw_len(base, mix["output_len"])
        prefix = None
        if shared and base.random() < float(shared["share"]):
            prefix = base.randrange(int(shared["count"]))
        return n_prompt, n_out, prefix

    if mix["kind"] == "open_loop":
        gaps, t = [], 0.0
        while True:
            g = base.expovariate(float(mix["rate_per_s"]))
            if t + g >= horizon:
                break
            gaps.append(g)
            t += g
        sizes = [size() for _ in gaps]
        _shuffle_blocks(order, gaps)
        dues, t = [], 0.0
        for g in gaps:
            t += g
            dues.append(t)
    elif mix["kind"] == "closed_loop":
        sizes = [size() for _ in range(int(mix.get("plan_requests", 4096)))]
        dues = [None] * len(sizes)
    else:
        raise ValueError(f"request_plan: kind {mix['kind']!r} sends no requests")
    _shuffle_blocks(order, sizes)

    prefixes = [_tokens(order, int(shared["tokens"]), vocab)
                for _ in range(int(shared["count"]))] if shared else []
    cap = int(mix.get("prompt_max", mix["prompt_len"].get("max", 1 << 30)))
    requests = []
    for i, ((n_prompt, n_out, prefix), due) in enumerate(zip(sizes, dues)):
        body = _tokens(order, n_prompt, vocab)
        prompt = (prefixes[prefix] + body)[:cap] if prefix is not None else body
        requests.append({"id": i, "due_s": due, "prompt": prompt,
                         "n_out": n_out, "prefix": prefix})
    return {"requests": requests, "prefixes": prefixes}


def train_batches(mix: Dict, seed: int, global_batch: int,
                  vocab: int) -> Iterator[Dict]:
    """Packed ``[global_batch, seq]`` batches of uniform random token ids,
    endless; the keys the program's own loader yields (tokens, labels,
    loss_mask, position_ids)."""
    import numpy as np

    seq = int(mix["seq_length"])
    rng = np.random.default_rng(int(seed) & SEED_MASK)
    ones = np.ones((global_batch, seq), np.float32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (global_batch, 1))
    while True:
        full = rng.integers(1, vocab, size=(global_batch, seq + 1),
                            dtype=np.int32)
        yield {"tokens": full[:, :-1], "labels": full[:, 1:],
               "loss_mask": ones, "position_ids": pos}


def probe_tokens(seed: int, rows: int, n: int, vocab: int) -> List[List[int]]:
    """The seeded sequences the correctness comparison runs on."""
    rng = random.Random((int(seed) & SEED_MASK) ^ 0x5EED)
    return [_tokens(rng, n, vocab) for _ in range(rows)]
