#!/usr/bin/env python3
"""What a serving cell's reference comparison reads, and what it is known to
fail, at one seed and with no measured window.

    python3 benchmark/control.py --workload <name> --seed <n> [--drop-keys-older-than N] [--tokens]

Builds the cell's weights, engine and server as a run does, streams the
cell's probes through the HTTP API, and holds three things to the cell's
own limits (``lib/check.py`` ``compare``), one JSON line in all:

* ``program``: the log-probabilities the engine streamed against the
  float32 reference: what a run's ``correct`` compares;
* ``control``: the reference itself computed in bfloat16 throughout
  (weights, activations, router, softmax, norms, the log-softmax) against
  itself in float32, at the same prompts and tokens: the precision below
  the configuration's, which the limits must not pass;
* ``dropped_keys`` (with the option): the program against a reference that
  forgets every key more than N positions behind its query: a fault that
  only a probe longer than N can show.

A limit of a configuration's ``tolerance`` lies between the ``program``
readings and the ``control`` readings over a dozen seeds.  Exits 2 off the
TPU (``--rehearsal 1``: the CPU at tiny widths, never a reading).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--drop-keys-older-than", type=int, default=None, metavar="N")
    ap.add_argument("--tokens", action="store_true",
                    help="add every compared token's absolute difference")
    ap.add_argument("--rehearsal", type=int, default=0)
    args = ap.parse_args()
    args.rate, args.trace = None, 0
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark.lib import cells, check, serving
    from benchmark.reference import common
    from megatron_llm_tpu.utils.platform import enable_compilation_cache

    cell = cells.Cell(args.workload)
    device = jax.devices()[0]
    if device.platform != ("cpu" if args.rehearsal else "tpu"):
        print("control: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    served = serving.Served(cell, args)
    try:
        probes, _ = served.stream_probes(args.seed)
    finally:
        served.server.stop()
    got = [lp for p in probes for lp in p["logprobs"]]
    in_use = (device.memory_stats() or {}).get("bytes_in_use")
    t = time.monotonic()
    want = check.emitted_reference(cell, served.params, probes)
    line = {"workload": cell.name, "seed": args.seed,
            "probe_lengths": [len(p["prompt"]) for p in probes],
            "program": check.compare(cell, got, want),
            "reference_s": time.monotonic() - t,
            "bytes_in_use_before_reference": in_use,
            "peak_bytes_in_use_after_reference":
                (device.memory_stats() or {}).get("peak_bytes_in_use"),
            "bytes_limit": (device.memory_stats() or {}).get("bytes_limit")}
    with mock.patch.object(common, "F32", jnp.bfloat16):
        low = check.emitted_reference(cell, served.params, probes)
    line["control"] = check.compare(cell, low, want)
    if args.tokens:      # every compared token's difference, for a new statistic
        line["abs_diffs"] = {
            name: [round(abs(a - b), 5) for a, b in zip(lp, want)]
            for name, lp in (("program", got), ("control", low))}
    if args.drop_keys_older_than:
        mask = common.causal_mask
        with mock.patch.object(
                common, "causal_mask", lambda start, size, s, window: mask(
                    start, size, s, args.drop_keys_older_than)):
            forgetful = check.emitted_reference(cell, served.params, probes)
        line["dropped_keys"] = check.compare(cell, got, forgetful)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
