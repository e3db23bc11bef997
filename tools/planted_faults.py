#!/usr/bin/env python3
"""Planted faults of a training cell's reference comparison, on the chip.

    chiprun -- python tools/planted_faults.py --workload smallthinker_train_16k --seed N

Builds the cell's parameters at its real size from the seed and trains them
through ``training.pretrain`` for ``--steps`` steps on the cell's own batches
(about what a measured run has made when it compares: its warm-up and its
window; on random tokens every step flattens the logits, so the state
decides what a difference reads; 0 = the initialiser's weights), runs the
reference once on the cell's probe positions, and holds against it, by the
cell's own limits
(``benchmark/lib/check.py`` ``compare``): the honest program, the program
with each fault planted through its flags, and the reference itself computed
in bfloat16 throughout (the precision below the configuration's).  Prints one
JSON line a variant; a fault that reads ``reference_ok`` true is a limit too
wide.  Exits 2 off the TPU.  Not a measurement of speed."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what ISSUE 33 plants in the patterned, expert-sharded stack
FAULTS = {
    "none": {},
    "window_ignored": {"sliding_window_layout": "0 0 0 0"},
    "rope_in_the_full_layers": {"rope_layout": "1 1 1 1"},
    "router_fed_the_post_attention_norm": {
        "moe_router_input": "post_attention"},
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=75)
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import cells, check, traffic
    from benchmark.reference import common
    from megatron_llm_tpu.config.arguments import parse_args
    from megatron_llm_tpu.models import init_model_params
    from megatron_llm_tpu.models.language_model import model_forward

    if jax.devices()[0].platform != ("cpu" if args.rehearsal else "tpu"):
        print("planted_faults: needs a TPU", file=sys.stderr)
        return 2
    cell = cells.Cell(args.workload)
    mix = dict(cell.traffic)
    base = {}
    if args.rehearsal:
        base.update(cell.config["rehearsal"]["flags"])
        cell.model.update(cell.config["rehearsal"]["model"])
        mix.update(mix["rehearsal"])
    positions = int(mix["probe_positions"])
    base.update(seq_length=positions, micro_batch_size=1, global_batch_size=1)

    def cfg_for(extra):
        argv = cell.flags({**base, **extra})
        # a layout is a list on the command line
        out = []
        for a in argv:
            out += a.split(" ") if " " in a else [a]
        return parse_args(out, n_devices=1)

    cfg = cfg_for({})
    seed = int(args.seed) % (2 ** 31 - 1)
    if args.steps:
        from megatron_llm_tpu.training import pretrain

        train_cfg = cfg_for(dict(
            seq_length=int(mix["seq_length"]), train_iters=args.steps,
            eval_iters=0, eval_interval=10 ** 9, log_interval=10 ** 9,
            seed=seed))

        def provider(_cfg, _tokenizer, _consumed):
            return traffic.train_batches(
                mix, args.seed, 1, train_cfg.model.vocab_size), None

        result = pretrain(train_cfg, data_iterators_provider=provider)
        params = result["params"]
        print(f"planted_faults: trained {result['iteration']} steps, last "
              f"loss {result['loss_series'][-1][1]:.4f}", flush=True)
    else:
        params = jax.jit(lambda k: init_model_params(cfg, k))(
            jax.random.PRNGKey(seed))
    toks = np.asarray(traffic.probe_tokens(
        args.seed, 1, positions + 1, cfg.model.vocab_size), np.int32)
    ref_lp = np.asarray(jax.device_get(
        check.reference_log_probs(cell, params, jnp.asarray(toks))))

    def report(name, lp):
        print(json.dumps({"variant": name, "seed": args.seed,
                          "steps": args.steps,
                          **check.compare(cell, lp, ref_lp)}), flush=True)

    def program_log_probs(c):
        """The program's per-token log-probabilities under config ``c``."""
        loss = jax.jit(lambda p, t: model_forward(
            c, p, t[:, :-1], labels=t[:, 1:])[0])(params, jnp.asarray(toks))
        return -np.asarray(jax.device_get(loss))

    for name, extra in FAULTS.items():
        report(name, program_log_probs(cfg_for(extra)))
    # the reference in the precision below: bfloat16 weights, activations,
    # router, softmax and norms
    f32 = common.F32
    common.F32 = jnp.bfloat16
    try:
        low = check.reference_log_probs(cell, params, jnp.asarray(toks))
        report("reference_in_bf16_throughout",
               np.asarray(jax.device_get(low)).astype(np.float32))
    finally:
        common.F32 = f32
    return 0


if __name__ == "__main__":
    sys.exit(main())
