#!/usr/bin/env python3
"""What a serving cell's reference comparison reads and what it is known to
fail, at one seed and with no measured window.

    chiprun -- python tools/serve_faults.py --workload commanda_plus_agent_16k --seed N
    chiprun -- python tools/serve_faults.py --workload brumby14b_longgen_closed --seed N

As ``benchmark/control.py`` (which it follows line by line and cannot be a
part of: a ``model_config`` PR adds to the benchmark and edits none of its
files): builds the cell's weights, engine and server as a run does, streams
the cell's probes through the HTTP API, and holds to the cell's own limits
(``benchmark/lib/check.py`` ``compare``), one JSON line in all:

* ``program``: the log-probabilities the engine streamed against the float32
  reference: what a run's ``correct`` compares;
* ``control``: the reference computed in bfloat16 throughout against itself
  in float32 at the same prompts and tokens: the precision below the
  configuration's, which the limits must not pass;
* one entry a planted fault: the program against a reference that has the
  fault (the difference is the one an honest reference reads of a program
  with it).  The faults are the three choices the reference module exposes
  (``benchmark/reference/commanda_block.py``): window layers that see every
  key, rotation on the full layer, the shared experts summed and not
  averaged; and the three of ``benchmark/reference/brumby_block.py``: the
  gate ignored (no decay), degree 1, the normaliser dropped.  A fault is
  tried where the cell's reference has its choice.

A limit of the configuration's ``tolerance`` lies between the ``program``
readings and the others over a dozen seeds; an entry other than ``program``
that reads ``reference_ok`` true is a limit too wide.  Exits 2 off the TPU
(``--rehearsal 1``: the CPU at tiny widths, never a reading)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# fault -> (the reference's choice, what takes its place)
FAULTS = {
    "window_layers_see_every_key": ("window_of", lambda model, kind: None),
    "rotation_on_the_full_layer": ("rotates", lambda model, kind: True),
    "shared_experts_summed": ("shared_scale", lambda model: 1.0),
    "gate_ignored": ("log_decay", lambda gate, u: 0.0 * (
        u @ gate["kernel"].astype(u.dtype))),
    "degree_one": ("degree", lambda model: 1),
    "normaliser_dropped": ("normalised", lambda model: False),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
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
        print("serve_faults: needs a TPU", file=sys.stderr)
        return 2
    enable_compilation_cache()
    served = serving.Served(cell, args)
    try:
        probes, hits = served.stream_probes(args.seed)
    finally:
        served.server.stop()
    ref = check.reference_module(cell)
    got = [lp for p in probes for lp in p["logprobs"]]
    t = time.monotonic()
    want = check.emitted_reference(cell, served.params, probes)
    line = {"workload": cell.name, "seed": args.seed,
            "probe_lengths": [len(p["prompt"]) for p in probes],
            "probe_prefix_hit_tokens": hits,
            "program": check.compare(cell, got, want),
            "reference_s": time.monotonic() - t,
            "peak_bytes_in_use_after_reference":
                (device.memory_stats() or {}).get("peak_bytes_in_use"),
            "bytes_limit": (device.memory_stats() or {}).get("bytes_limit")}
    with mock.patch.object(common, "F32", jnp.bfloat16):
        low = check.emitted_reference(cell, served.params, probes)
    line["control"] = check.compare(cell, low, want)
    for name, (choice, fault) in FAULTS.items():
        if not hasattr(ref, choice):
            continue
        # the reference jits its layer program anew every call, so the
        # choice is traced in
        with mock.patch.object(ref, choice, fault):
            faulty = check.emitted_reference(cell, served.params, probes)
        line[name] = check.compare(cell, got, faulty)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
